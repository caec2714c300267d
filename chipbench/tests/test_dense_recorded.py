"""The dense family reproduces, bit for bit, what the harness made before
families existed: a digest of every leaf of the engine's parameter tree,
the reference's gaps (float32 and the fp8 control) over fixed prompts
and served tokens, and a digest of the logits of each call of the
reference's head in those gaps, with its mode (at these sizes the fp8
control puts the reference's own token first, so its gaps are mostly 0
and only its logits pin its numerics), at both tiny configurations.
The recorded values were made with the helpers below by the harness of
the commit before the family interface (``weights.program_params`` and
``reference.gaps`` called directly)."""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from tiny import SPECS

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded", "dense-tiny.json")
SEED = 2**31 + 303


def inputs(spec):
    """Two sequences, one past a padding bucket, with the served tokens
    starting at fixed positions."""
    rng = np.random.default_rng(20261019)
    seqs = [rng.integers(0, spec.vocab, n).astype(np.int32)
            for n in (41, 530)]
    return seqs, [29, 402]


def digest(leaf) -> str:
    """sha256 of an array's dtype, shape and bytes."""
    a = np.asarray(leaf)
    h = hashlib.sha256(f"{a.dtype}{a.shape}".encode())
    h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def digests(params) -> dict:
    """The digest of each leaf, by its path."""
    return {jax.tree_util.keystr(path): digest(leaf) for path, leaf
            in jax.tree_util.tree_flatten_with_path(params)[0]}


def head_calls(reference, gaps, spec) -> list:
    """``[mode, digest of the logits]`` of every call of the reference's
    head, in order, while ``gaps`` compares with the control."""
    seqs, starts = inputs(spec)
    head, calls = reference._head_jit, []

    def recording(sizes, mode, hw, x):
        out = head(sizes, mode, hw, x)
        calls.append([mode, digest(out)])
        return out

    reference._head_jit = recording
    try:
        gaps(spec, SEED, seqs, starts, control=True)
    finally:
        reference._head_jit = head
    return calls


def record(program_params, gaps, reference) -> dict:
    """What is recorded, for each tiny configuration."""
    out = {}
    for arch, spec in sorted(SPECS.items()):
        seqs, starts = inputs(spec)
        f32 = gaps(spec, SEED, seqs, starts)
        served, ctrl = gaps(spec, SEED, seqs, starts, control=True)
        out[arch] = {
            "params": digests(program_params(spec, SEED)),
            "f32": [np.asarray(g, np.float32).tolist() for g in f32],
            "f32_in_control_run": [np.asarray(g, np.float32).tolist()
                                   for g in served],
            "control": [np.asarray(g, np.float32).tolist() for g in ctrl],
            "head_calls": head_calls(reference, gaps, spec),
        }
    return out


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def dense():
    import family
    return family.load("dense")


@pytest.mark.parametrize("arch", sorted(SPECS))
def test_program_params_bit_for_bit(arch, recorded, dense):
    assert digests(dense.program_params(SPECS[arch], SEED)) == \
        recorded[arch]["params"]


@pytest.mark.parametrize("arch", sorted(SPECS))
def test_gaps_bit_for_bit(arch, recorded, dense):
    spec = SPECS[arch]
    seqs, starts = inputs(spec)
    want = recorded[arch]
    f32 = dense.gaps(spec, SEED, seqs, starts)
    served, ctrl = dense.gaps(spec, SEED, seqs, starts, control=True)
    for key, got in (("f32", f32), ("f32_in_control_run", served),
                     ("control", ctrl)):
        assert len(got) == len(want[key])
        for g, w in zip(got, want[key]):
            np.testing.assert_array_equal(g, np.asarray(w, np.float32))


@pytest.mark.parametrize("arch", sorted(SPECS))
def test_head_logits_bit_for_bit(arch, recorded, dense):
    import reference
    calls = head_calls(reference, dense.gaps, SPECS[arch])
    assert calls == recorded[arch]["head_calls"]
    assert [m for m, _ in calls].count("fp8") == len(inputs(SPECS[arch])[0])
