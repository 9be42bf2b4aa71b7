"""How the benchmark hands a dense decoder configuration to the program.

``model_config`` builds the program's ``ModelConfig`` from the sizes in the
configuration file (not from the program's own registry, so the cell runs
exactly what the file states). ``to_program`` maps the reference layout of
``reference/dense_decoder.py`` onto the program's parameter tree, and
``from_program`` maps it back, leaf by leaf, for the comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# program leaf (inside params["stages"][0][0]) <- reference layer key
LAYER_MAP = {
    ("norm1",): "attention_norm",
    ("attn", "wq"): "wq", ("attn", "wk"): "wk", ("attn", "wv"): "wv",
    ("attn", "wo"): "wo",
    ("norm2",): "ffn_norm",
    ("ffn", "w_gate"): "w1", ("ffn", "w_up"): "w3", ("ffn", "w_down"): "w2",
}


def model_config(cfg: dict):
    from repro.configs import ModelConfig

    n = cfg["num_hidden_layers"]
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="dense", d_model=d, n_layers=n,
        vocab_size=cfg["vocab_size"], stages=((("attn",), n),),
        n_heads=h, n_kv_heads=cfg["num_key_value_heads"], head_dim=d // h,
        d_ff=cfg["intermediate_size"], qkv_bias=bool(cfg.get("bias", False)),
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        source=cfg["source"])


def to_program(ref: dict) -> dict:
    layer = {}
    for path, name in LAYER_MAP.items():
        node = layer
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = ref["layers"][name]
    return {"embed": ref["tok_embeddings"], "stages": [[layer]],
            "final_norm": ref["norm"], "head": ref["output"]}


def from_program(tree: dict) -> dict:
    layer = tree["stages"][0][0]
    layers = {}
    for path, name in LAYER_MAP.items():
        node = layer
        for p in path:
            node = node[p]
        layers[name] = node
    return {"tok_embeddings": tree["embed"], "layers": layers,
            "norm": tree["final_norm"], "output": tree["head"]}


def leaves(ref_tree: dict) -> dict[str, jax.Array]:
    """Named leaves for the comparison: stacked layer weights are split
    into one leaf per layer (``layers.wq.3``)."""
    out = {"tok_embeddings": ref_tree["tok_embeddings"],
           "norm": ref_tree["norm"], "output": ref_tree["output"]}
    for name, a in ref_tree["layers"].items():
        for i in range(a.shape[0]):
            out[f"layers.{name}.{i}"] = a[i]
    return out


def leaf_norms(ref_tree: dict) -> dict[str, jax.Array]:
    return {k: jnp.linalg.norm(v.astype(jnp.float32).ravel())
            for k, v in leaves(ref_tree).items()}


def matmul_flops_per_token(cfg: dict) -> float:
    """Forward FLOPs of one token's matrix products: 2 per weight, the head
    included and the embedding lookup not."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    dh = d // h
    kv = cfg["num_key_value_heads"]
    per_layer = d * (h + 2 * kv) * dh + h * dh * d + 3 * d * cfg["intermediate_size"]
    return 2.0 * (cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"])


def attention_flops(cfg: dict, n_keys: float) -> float:
    """Forward FLOPs of one query over ``n_keys`` keys, all layers (Q K^T
    and P V)."""
    return 4.0 * cfg["num_hidden_layers"] * cfg["hidden_size"] * n_keys


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Training FLOPs of one token of a causal sequence of ``seq_len``:
    forward and backward (3x the forward), nothing recomputed counted."""
    return 3.0 * (matmul_flops_per_token(cfg)
                  + attention_flops(cfg, (seq_len + 1) / 2))
