"""A configuration's family is found by name: a family brought as new
files alone (a module and a configuration that names it, outside the
harness's own directories) runs a whole tiny run on the CPU and reads
correct; a configuration without the key is dense; an unknown family
fails at load and names the file it looked for."""
import json
import textwrap

import pytest

import family
import run
from tiny import DECODE

SEED = 2**31 + 29

TOY_FAMILY = textwrap.dedent('''
    """A toy family: the dense one under sizes of its own, with one more
    leaf in the parameter tree that the forward never reads, and its own
    `build_engine`."""
    from __future__ import annotations

    from dataclasses import dataclass

    import jax.numpy as jnp

    import family
    import system

    DENSE = family.load("dense")
    built = []


    @dataclass(frozen=True)
    class Sizes:
        dense: object
        name: str
        layers: int
        vocab: int
        program_arch: str
        family: str = "toy"


    def spec(raw, name):
        d = DENSE.spec(raw, name)
        return Sizes(dense=d, name=name, layers=d.layers, vocab=d.vocab,
                     program_arch=d.program_arch)


    def model_config(sizes):
        return DENSE.model_config(sizes.dense)


    def program_params(sizes, seed):
        params = DENSE.program_params(sizes.dense, seed)
        params["toy_unread"] = jnp.ones((3,), jnp.float32)
        return params


    def gaps(sizes, seed, seqs, served_from, control=False):
        return DENSE.gaps(sizes.dense, seed, seqs, served_from, control)


    def build_engine(params, cfg, eng, seed):
        built.append(sorted(params))
        return system.build_engine(params, cfg, eng, seed)
''')

TOY_CONFIG = {
    "harness_family": "toy", "model_type": "qwen3",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 128, "num_hidden_layers": 2,
    "vocab_size": 97, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "program_arch": "qwen3-8b",
}


@pytest.fixture
def roots(tmp_path, monkeypatch):
    """A directory of new files, looked in before the harness's own."""
    (tmp_path / "archs").mkdir()
    (tmp_path / "configs").mkdir()
    monkeypatch.setattr(family, "ROOTS", [str(tmp_path)] + family.ROOTS)
    return tmp_path


def _config(roots, name, raw):
    with open(roots / "configs" / f"{name}.json", "w") as f:
        json.dump(raw, f)


def test_family_added_as_files_alone(roots):
    (roots / "archs" / "toy.py").write_text(TOY_FAMILY)
    _config(roots, "toy-tiny", TOY_CONFIG)
    spec = family.load_config("toy-tiny")
    toy = family.load("toy")
    assert isinstance(spec, toy.Sizes)
    assert spec.family == "toy" and spec.layers == 2 and spec.vocab == 97
    mix = dict(DECODE, engine=dict(DECODE["engine"], num_r_workers=1))
    result, checks, _ = run.execute(spec, mix, SEED, 1.0, False, [], {},
                                    say=lambda m: None, cache=False)
    assert result["correct"], checks
    assert checks["tokens_compared"]["value"] >= mix["check"]["min_tokens"]
    assert toy.built and "toy_unread" in toy.built[0]


def test_configuration_without_family_is_dense():
    spec = family.load_config("qwen3-8b")
    assert spec.family == "dense"
    assert isinstance(spec, family.load("dense").Spec)


def test_unknown_family_fails_at_load(roots):
    _config(roots, "stray", dict(TOY_CONFIG, harness_family="no-such"))
    with pytest.raises(ValueError, match="no-such") as err:
        family.load_config("stray")
    assert str(roots / "archs" / "no-such.py") in str(err.value)
    assert str(err.value).count("no-such.py") == len(family.ROOTS)
