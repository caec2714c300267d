"""A dense configuration file, read as the sizes the benchmark needs.

The file under ``chipbench/configs/<name>.json`` holds the published
``config.json`` keys (with the depth cut listed under ``reduced``) plus
the benchmark's own notes; ``family.load_config`` reads it.  Nothing
here imports the program.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Spec:
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    tied: bool
    qk_norm: bool
    dtype: str
    program_arch: str
    family: str = "dense"

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V bytes one token holds over all layers (bf16)."""
        return 2 * self.kv_heads * self.head_dim * 2 * self.layers

    def layer_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * (self.heads + 2 * self.kv_heads) * hd + self.heads * hd * d
        return attn + 3 * d * self.d_ff

    def head_params(self) -> int:
        return self.vocab * self.d_model


def from_dict(raw: dict, name: str = "") -> Spec:
    d = int(raw["hidden_size"])
    heads = int(raw["num_attention_heads"])
    dtype = raw.get("torch_dtype", "bfloat16")
    if dtype != "bfloat16":
        raise ValueError(f"{name}: only bfloat16 configurations are served")
    return Spec(
        name=name or raw.get("program_arch", ""),
        layers=int(raw["num_hidden_layers"]),
        d_model=d, heads=heads,
        kv_heads=int(raw["num_key_value_heads"]),
        head_dim=int(raw.get("head_dim") or d // heads),
        d_ff=int(raw["intermediate_size"]),
        vocab=int(raw["vocab_size"]),
        rope_theta=float(raw["rope_theta"]),
        norm_eps=float(raw["rms_norm_eps"]),
        tied=bool(raw["tie_word_embeddings"]),
        qk_norm=raw.get("model_type") == "qwen3",
        dtype=dtype,
        program_arch=raw["program_arch"])
