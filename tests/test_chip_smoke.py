"""chip_smoke.py's path at tiny widths on the CPU, and the rules PR 21 set so
that nothing on that path hides the device: the compile-cache rule, the
explicit decode kernel, the roofline table, one quantized load path."""

import asyncio
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import chip_smoke
from nats_llm_studio_tpu.config import DEFAULT_COMPILE_CACHE_DIR, WorkerConfig
from nats_llm_studio_tpu.models.config import ModelConfig

REPO = Path(__file__).resolve().parent.parent


def test_smoke_path_serves_at_tiny_widths(tmp_path, monkeypatch):
    """generate -> start_serve -> drive over NATS, the function main() runs
    on the chip; the device check is main()'s, so the CPU can rehearse it."""
    for k in ("LMSTUDIO_MODELS_DIR", "WQUANT"):  # the function sets both
        monkeypatch.setenv(k, "")
    # one device, as on the one-chip machine (conftest forces 8 virtual ones,
    # and auto would put the 4-head toy on tp=8)
    monkeypatch.setenv("MESH_SHAPE", "off")
    cfg = ModelConfig.tiny(max_seq_len=1024)
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        out = asyncio.run(asyncio.wait_for(
            chip_smoke.serve_and_drive(cfg, tmp_path, seed=0), timeout=240.0))
    finally:  # start_serve's configure_jax persists every program; tests do not
        jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)
    assert out["gguf"]["tensors"] == 3 + 9 * cfg.n_layers
    assert {d["platform"] for d in out["devices"]} == {"cpu"}
    # first, stream, four concurrent (one long), long alone, stream + long
    # under it, warm — back to back, all ok envelopes, nothing shed
    assert len(out["replies"]) == 10
    assert all(r["completion_tokens"] == chip_smoke.MAX_NEW for r in out["replies"].values())
    assert out["replies"]["long"]["prompt_tokens"] >= 300
    assert out["engine"]["decode_kernel_pallas"] == 0  # auto off-TPU: xla
    assert out["engine"]["spec_verifies"] > 0
    assert out["engine"]["shed_by_cause"] == {}
    assert out["weight_bytes"]


def test_smoke_refuses_the_cpu():
    p = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=REPO, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True,
    )
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    assert "TPU" in p.stderr


def test_serve_refuses_an_unrequested_cpu():
    """No JAX_PLATFORMS, no accelerator: JAX falls back to the CPU with a
    warning, and ``serve`` must exit instead of answering from it."""
    p = subprocess.run(
        [sys.executable, "-m", "nats_llm_studio_tpu", "serve", "--embedded-broker",
         "--port", "0"],
        cwd=REPO, timeout=120, env={"PATH": "/usr/bin:/bin", "TPU_LOG_DIR": "disabled"},
        capture_output=True, text=True,
    )
    assert p.returncode != 0
    assert "'cpu' backend, which was not asked for" in p.stderr


@pytest.mark.parametrize("placed", [True, False], ids=["env_places_it", "fixed_path"])
def test_configure_jax_compile_cache_rule(monkeypatch, placed):
    """JAX_COMPILATION_CACHE_DIR set: JAX honours it and the program sets no
    directory. Unset: one fixed path inside the checkout."""
    calls = {}
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.__setitem__(k, v))
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    WorkerConfig().configure_jax()
    if placed:
        assert "jax_compilation_cache_dir" not in calls
    else:
        assert calls["jax_compilation_cache_dir"] == DEFAULT_COMPILE_CACHE_DIR
        assert Path(DEFAULT_COMPILE_CACHE_DIR).parent == REPO
    assert calls["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_explicit_pallas_on_ineligible_layout_raises(monkeypatch):
    """DECODE_KERNEL=pallas is an order: where the heads cannot split
    (Hkv=2 on tp=4, the replicated-KV fallback) construction raises, and
    auto still downshifts."""
    from nats_llm_studio_tpu.models.llama import init_params
    from nats_llm_studio_tpu.parallel import build_mesh
    from nats_llm_studio_tpu.parallel.sharding import shard_params
    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

    cfg = ModelConfig.tiny(n_layers=1, max_seq_len=64)
    mesh = build_mesh("tp=4", devices=jax.devices()[:4])
    params = shard_params(init_params(cfg, jax.random.PRNGKey(0)), mesh, cfg)

    def kernel():
        b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64, mesh=mesh, paged=True)
        b.stop()
        return b.decode_kernel

    monkeypatch.setenv("DECODE_KERNEL", "pallas")
    with pytest.raises(ValueError, match="DECODE_KERNEL=pallas cannot serve"):
        kernel()
    monkeypatch.setenv("DECODE_KERNEL", "auto")
    assert kernel() == "xla"


def test_unsharded_quantized_load_streams(tmp_path, monkeypatch):
    """registry._load has one load path: with mesh=None and quant="int8" a
    Q8_0 GGUF goes through the streaming loader (host requantization, then
    placement — never the whole bf16 tree on the device first) and yields
    the same QTensor tree as the mesh path on one device."""
    from nats_llm_studio_tpu.gguf.reader import open_gguf
    from nats_llm_studio_tpu.ops.wquant import QTensor
    from nats_llm_studio_tpu.parallel import build_mesh, loader
    from nats_llm_studio_tpu.serve.registry import LocalRegistry
    from nats_llm_studio_tpu.store.manager import ModelStore

    cfg = ModelConfig.tiny(n_layers=2)
    path = tmp_path / "models" / chip_smoke.MODEL_ID / "m.gguf"
    chip_smoke.generate_gguf(cfg, path, seed=3)
    streamed = []
    real = loader.load_params_sharded
    monkeypatch.setattr(
        loader, "load_params_sharded",
        lambda *a, **kw: streamed.append(kw) or real(*a, **kw),
    )
    registry = LocalRegistry(ModelStore(tmp_path / "models"), mesh=None,
                             quant="int8", dtype="float32", max_seq_len=64,
                             max_batch_slots=2)
    eng = registry._load(chip_smoke.MODEL_ID, [str(path)])
    try:
        got = eng.batcher.params
    finally:
        eng.batcher.stop()
    assert [kw["quant"] for kw in streamed] == ["int8"]
    with open_gguf(str(path)) as reader:
        want = real(reader, cfg, build_mesh({"tp": 1}, devices=jax.devices()[:1]),
                    quant="int8")
    assert isinstance(got["blocks"]["wq"], QTensor) and isinstance(got["lm_head"], QTensor)
    assert got["embed"].dtype == np.float32
    got_leaves, got_def = jax.tree.flatten(got)
    want_leaves, want_def = jax.tree.flatten(want)
    assert got_def == want_def
    for a, b in zip(got_leaves, want_leaves):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _tp_run(top: dict[str, float], weights: dict[str, int], in_use: dict[int, int]) -> dict:
    reply = lambda n, shift=0.0: {  # noqa: E731
        "prompt_tokens": n, "completion_tokens": 32, "finish_reason": "length",
        "logprobs": {"content": [{"top_logprobs": [
            {"token": t, "logprob": lp + shift} for t, lp in top.items()]}]},
    }
    return {
        "replies": {"first": reply(68), "stream": reply(131), "warm": reply(68),
                    "long": reply(547), "long_under_decode": reply(547)},
        "weight_bytes": weights,
        "memory_end": [{"id": d, "bytes_in_use": n} for d, n in in_use.items()],
    }


@pytest.mark.parametrize("shift,long_shift,ok", [
    (0.5, 0.9, True),     # what a bf16 reorder explains
    (0.5, 1.2, False),    # the long prompts alone sit past their median bound
    (2.6, 0.5, False),    # one request past the per-request bound
], ids=["reorder", "long_median", "single_request"])
def test_compare_tp_bounds(shift, long_shift, ok):
    """--chips 4's comparison: per-request and per-case median bounds on the
    first token's top-5 logprobs, and ~1/4 of the bytes on each device."""
    top = {"a": -0.2, "b": -2.0, "c": -3.0, "d": -4.0, "e": -5.0}
    one = _tp_run(top, {"0": 8000}, {0: 12000})
    tp = _tp_run(top, {str(d): 2700 for d in range(4)}, {d: 3700 for d in range(4)})
    for key, r in tp["replies"].items():
        bump = long_shift if r["prompt_tokens"] >= chip_smoke.LONG_TOKENS else 0.5
        if key == "first":
            bump = shift
        for t in r["logprobs"]["content"][0]["top_logprobs"]:
            t["logprob"] -= bump
    if ok:
        out = chip_smoke.compare_tp(one, tp, 4)
        assert out["share_of_one_chip_bytes"]["kv_pool"]["3"] == 0.25
    else:
        with pytest.raises(RuntimeError, match="logprobs differ"):
            chip_smoke.compare_tp(one, tp, 4)
