"""End-to-end behaviour tests of the whole system (public API surface)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config, list_configs
from repro.core import PammPolicy, qkv_activation_bytes


def test_all_assigned_archs_registered():
    names = list_configs()
    for arch in ASSIGNED_ARCHS:
        assert arch in names
        assert arch + "_smoke" in names


def test_assigned_configs_exact():
    """The configs must match the assignment brief verbatim."""
    want = {
        "granite-moe-3b-a800m": dict(n_layers=32, d_model=1536, n_heads=24,
                                     n_kv_heads=8, vocab_size=49155,
                                     n_experts=40, n_experts_per_tok=8),
        "kimi-k2-1t-a32b": dict(n_layers=61, d_model=7168, n_heads=64,
                                n_kv_heads=8, vocab_size=163840, n_experts=384,
                                n_experts_per_tok=8, moe_d_ff=2048),
        "internlm2-1.8b": dict(n_layers=24, d_model=2048, n_heads=16,
                               n_kv_heads=8, d_ff=8192, vocab_size=92544),
        "qwen2-72b": dict(n_layers=80, d_model=8192, n_heads=64, n_kv_heads=8,
                          d_ff=29568, vocab_size=152064, qkv_bias=True),
        "h2o-danube-3-4b": dict(n_layers=24, d_model=3840, n_heads=32,
                                n_kv_heads=8, d_ff=10240, vocab_size=32000),
        "qwen3-32b": dict(n_layers=64, d_model=5120, n_heads=64, n_kv_heads=8,
                          d_ff=25600, vocab_size=151936, qk_norm=True),
        "recurrentgemma-9b": dict(n_layers=38, d_model=4096, n_heads=16,
                                  n_kv_heads=1, d_ff=12288, vocab_size=256000),
        "llama-3.2-vision-11b": dict(n_layers=40, d_model=4096, n_heads=32,
                                     n_kv_heads=8, d_ff=14336, vocab_size=128256),
        "musicgen-medium": dict(n_layers=48, d_model=1536, n_heads=24,
                                n_kv_heads=24, d_ff=6144, vocab_size=2048,
                                n_codebooks=4),
        "mamba2-370m": dict(n_layers=48, d_model=1024, vocab_size=50280,
                            ssm_state=128),
    }
    for arch, fields in want.items():
        cfg = get_config(arch)
        for k, v in fields.items():
            assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)


def test_shapes_table():
    assert [s[0] for s in SHAPES] == ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
    assert SHAPES[0][1:] == (4096, 256, "train")
    assert SHAPES[3][1:] == (524288, 1, "decode")


def test_paper_memory_claim_llama1b():
    """Table 5: LLaMA-1B, r=1/512 -> QKV activations ~3 GB -> tens of MB.

    Paper trains 1B with DDP on 8 GPUs (global batch 512 -> 64/GPU, §4.4);
    Table 5 memory is per-GPU f32: 24L x 64x256 tokens x 2048 x 4B = 3.2 GB.
    PAMM at r=1/512 must save >97% (the paper's headline).
    """
    cfg = get_config("llama-1b")
    rep = qkv_activation_bytes(
        PammPolicy(ratio=1 / 512), n_layers=cfg.n_layers, batch=64, seq=256,
        hidden=cfg.d_model, dtype=jnp.float32,
    )
    gb = rep.baseline_bytes / 2**30
    assert 2.5 < gb < 3.5          # paper: 3 GB
    assert rep.compressed_bytes / 2**20 < 40   # paper: 24 MB
    assert rep.saving > 0.97        # paper: >97%


def test_cli_train_entrypoint():
    """The production launcher runs end-to-end (tiny arch, few steps)."""
    import os

    env = dict(os.environ)
    env.update({"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "internlm2-1.8b_smoke", "--steps", "6", "--seq-len", "16",
         "--global-batch", "4", "--log-every", "2"],
        capture_output=True, text=True, timeout=300, env=env, cwd="/root/repo",
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "done:" in res.stdout


def test_compile_cache_follows_env_else_repo_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache goes to a fixed directory in the checkout."""
    from repro.launch import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.REPO_ROOT / ".jax_cache")
        assert (compile_cache.REPO_ROOT / "chip_smoke.py").is_file()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
