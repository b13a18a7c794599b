"""The main path's kernels compile for a TPU v5e at Llama-3-8B widths.

No chip is attached here: the TPU compiler that ships with jaxlib/libtpu
compiles for a *described* ``v5e:2x2`` topology, which is what refuses a
kernel Mosaic cannot tile, a program that does not fit 16 GB, or a kernel
that cannot be partitioned — none of which the Pallas interpreter the other
tests run under can see. Nothing executes; a compile that passes is not a
chip run.

The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU library, and every xdist worker imports
this file), everything built from it is built in fixtures or tests, and the
whole family lives in this one file so one worker owns the library.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from nats_llm_studio_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_chunk,
    flash_attention_chunk_kvq,
)
from nats_llm_studio_tpu.ops.kvcache import KVQ, kv_pool_write_rows
from nats_llm_studio_tpu.ops.paged_attention import (
    paged_decode_attention,
    paged_decode_eligible,
)

# Llama-3-8B attention geometry under the serving defaults: MAX_BATCH_SLOTS=8,
# MAX_SEQ_LEN=4096, KV_BLOCK_TOKENS=16, prefill chunk 256, SPEC_DECODE_K=6,
# pool = 8 x 256 blocks + 64 prefix blocks + the null block
L, HQ, HKV, D = 32, 32, 8, 128
SLOTS, SEQ, T, CHUNK, SPEC_W = 8, 4096, 16, 256, 7
POOL_BLOCKS = SLOTS * (SEQ // T) + 64 + 1
SCALE = D ** -0.5


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp4(topo):
    return Mesh(np.array(topo.devices), ("tp",))


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    # whether the cache is used is decided once a process: ask again, both ways
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # a Mosaic kernel, not interpret mode
    return compiled


def _pool(sharding, quantized: bool, t: int = T):
    shape = (POOL_BLOCKS, L, HKV, t, D)
    if not quantized:
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)
    return KVQ(q=jax.ShapeDtypeStruct(shape, jnp.int8, sharding=sharding),
               s=jax.ShapeDtypeStruct(shape[:-1], jnp.float32, sharding=sharding))


def _decode_args(sharding, w: int, quantized: bool, t: int = T):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)  # noqa: E731
    return (
        sds((SLOTS, w, HQ, D), jnp.bfloat16),
        _pool(sharding, quantized, t), _pool(sharding, quantized, t),
        sds((SLOTS, SEQ // t), jnp.int32), sds((SLOTS,), jnp.int32),
        sds((), jnp.int32),
    )


@pytest.mark.parametrize("w", [1, SPEC_W], ids=["decode", "spec_verify"])
def test_paged_decode_bf16(one_chip, no_cache, w):
    assert paged_decode_eligible(T, D, 2, False, HKV)
    _compile(
        lambda q, kp, vp, tbl, pos, layer: paged_decode_attention(
            q, kp, vp, tbl, pos, layer, SCALE),
        *_decode_args(one_chip, w, quantized=False),
    )


def test_paged_decode_at_the_benchmark_cells_shapes(one_chip, no_cache):
    """Granite-3.1-8B as both benchmark cells serve it: 8 slots, 8 kv heads,
    MAX_SEQ_LEN 2048 = table width 128, a 40-layer pool, the decode and the
    speculative widths. A VMEM overflow of the run's tiles shows here, not
    in a chip call."""
    layers, width = 40, 2048 // T
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    pool = sds((SLOTS * width + 64 + 1, layers, HKV, T, D), jnp.bfloat16)
    for w in (1, SPEC_W):
        _compile(
            lambda q, kp, vp, tbl, pos, layer: paged_decode_attention(
                q, kp, vp, tbl, pos, layer, SCALE),
            sds((SLOTS, w, HQ, D), jnp.bfloat16), pool, pool,
            sds((SLOTS, width), jnp.int32), sds((SLOTS,), jnp.int32),
            sds((), jnp.int32),
        )


@pytest.mark.parametrize("t", [16, 32])
def test_paged_decode_int8_kvq(one_chip, no_cache, t):
    """int8 codes at the default KV_BLOCK_TOKENS=16 compile too (the block's
    sublane extent IS the array's, which Mosaic always accepts), so the
    eligibility predicate must not downshift TPU_KV_QUANT=int8 to XLA."""
    assert paged_decode_eligible(t, D, 2, True, HKV)
    _compile(
        lambda q, kp, vp, tbl, pos, layer: paged_decode_attention(
            q, kp, vp, tbl, pos, layer, SCALE),
        *_decode_args(one_chip, 1, quantized=True, t=t),
    )


def test_pool_row_write_keeps_the_pool_in_place(one_chip, no_cache):
    """Write-then-attend as forward_decode_paged runs it, over a layer scan:
    the compiled program must not copy the pool. A scatter whose window
    spans the head axis made the TPU compiler re-lay the whole pool out for
    the write and back for the kernel, per layer (2 GB each at these
    widths)."""
    def step(q, rows, kp, vp, tbl, pos):
        def body(carry, layer):
            kp, vp = carry
            kp = kv_pool_write_rows(kp, rows, tbl, pos, layer)
            vp = kv_pool_write_rows(vp, rows, tbl, pos, layer)
            out = paged_decode_attention(q, kp, vp, tbl, pos, layer, SCALE)
            return (kp, vp), out.sum()

        (kp, vp), outs = jax.lax.scan(body, (kp, vp), jnp.arange(L, dtype=jnp.int32))
        return outs, kp, vp

    q, kp, vp, tbl, pos, _ = _decode_args(one_chip, 1, quantized=False)
    rows = jax.ShapeDtypeStruct((SLOTS, 1, HKV, D), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(step, donate_argnums=(2, 3)).lower(q, rows, kp, vp, tbl, pos).compile()
    pool_bytes = POOL_BLOCKS * L * HKV * T * D * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 4
    assert f"bf16[{POOL_BLOCKS},{L},{HKV},{T},{D}]" in compiled.as_text()
    assert not [
        ln for ln in compiled.as_text().splitlines()
        if " copy(" in ln and f"bf16[{POOL_BLOCKS}," in ln
    ]


def test_flash_attention_prefill(one_chip, no_cache):
    sds = lambda h: jax.ShapeDtypeStruct((1, 2048, h, D), jnp.bfloat16, sharding=one_chip)  # noqa: E731
    _compile(lambda q, k, v: flash_attention(q, k, v, SCALE), sds(HQ), sds(HKV), sds(HKV))


def test_flash_attention_chunk(one_chip, no_cache):
    q = jax.ShapeDtypeStruct((1, CHUNK, HQ, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, HKV, SEQ, D), jnp.bfloat16, sharding=one_chip)
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _compile(lambda q, k, v, st: flash_attention_chunk(q, k, v, SCALE, st), q, kv, kv, start)


def test_flash_attention_chunk_kvq(one_chip, no_cache):
    q = jax.ShapeDtypeStruct((1, CHUNK, HQ, D), jnp.bfloat16, sharding=one_chip)
    codes = jax.ShapeDtypeStruct((1, HKV, SEQ, D), jnp.int8, sharding=one_chip)
    scales = jax.ShapeDtypeStruct((1, HKV, SEQ), jnp.float32, sharding=one_chip)
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _compile(
        lambda q, kq, ks, vq, vs, st: flash_attention_chunk_kvq(q, kq, ks, vq, vs, SCALE, st),
        q, codes, scales, codes, scales, start,
    )


def test_paged_decode_shard_map_tp4(tp4, no_cache):
    """The tp serving path: the kernel under shard_map on four devices, two
    KV heads per shard (models/llama.py _paged_attn_dispatch)."""
    from nats_llm_studio_tpu.models.llama import _paged_attn_dispatch

    heads = lambda *spec: NamedSharding(tp4, P(*spec))  # noqa: E731
    rep = NamedSharding(tp4, P())
    pool = jax.ShapeDtypeStruct((POOL_BLOCKS, L, HKV, T, D), jnp.bfloat16,
                                sharding=heads(None, None, "tp", None, None))
    q = jax.ShapeDtypeStruct((SLOTS, 1, HQ, D), jnp.bfloat16,
                             sharding=heads(None, None, "tp", None))
    tbl = jax.ShapeDtypeStruct((SLOTS, SEQ // T), jnp.int32, sharding=rep)
    pos = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=rep)
    layer = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    # steer the program's backend check: lowering happens on the CPU
    # backend, and the kernel must not take its interpret branch
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        compiled = _compile(
            lambda q, kp, vp, tbl, pos, layer: _paged_attn_dispatch(
                q, kp, vp, tbl, pos, layer, SCALE, tp4),
            q, pool, pool, tbl, pos, layer,
        )
    finally:
        jax.default_backend = orig
    # per device: a quarter of each pool, nothing gathered
    per_dev = compiled.memory_analysis().argument_size_in_bytes
    assert per_dev < 0.3 * 2 * POOL_BLOCKS * L * HKV * T * D * 2
    assert "all-gather" not in compiled.as_text()


# (slots, block tokens, table width, pool layers) of the two cells that run the
# latent kernel: 32 query heads over ONE latent head of 512 + a rotary key of
# 64 in rows padded to the 128 lanes (a copy out of the pool cannot slice
# inside a lane tile: 64-wide rows are refused)
MLA_CELLS = {"xing29b.answer_closed": (SLOTS, T, SEQ // T, 7),
             "kanana2.longctx_closed": (16, 64, 32768 // 64, 6)}


@pytest.mark.parametrize("w", [1, SPEC_W], ids=["decode", "spec_verify"])
@pytest.mark.parametrize("cell", list(MLA_CELLS))
def test_mla_paged_decode_at_the_benchmark_cells_shapes(one_chip, no_cache, cell, w):
    """Xing4.0-29B-A4B as ``xing29b.answer_closed`` serves it (8 slots,
    MAX_SEQ_LEN 4096 in blocks of 16, a 7-layer pool pair) and kanana-2 as
    ``kanana2.longctx_closed`` does (16 slots of 32,768 in blocks of 64, 6
    layers): the table, the launch's live list and the landing buffers of a
    run of the rule's length fit the scalar memory and the VMEM Mosaic gives
    unasked."""
    from nats_llm_studio_tpu.ops.mla_attention import (
        mla_paged_decode_attention,
        mla_paged_decode_eligible,
    )
    from nats_llm_studio_tpu.ops.ssm_scan import LiveSlots

    slots, t, width, layers = MLA_CELLS[cell]
    r, dr = 512, 128
    assert mla_paged_decode_eligible(t, r, 2)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    blocks = slots * width + 64 + 1
    _compile(
        lambda qt, qr, cp, rp, tbl, pos, mask, order, n, layer: mla_paged_decode_attention(
            qt, qr, cp, rp, tbl, pos, LiveSlots(mask, order, n), layer, 0.1447),
        sds((slots, w, HQ, r), jnp.bfloat16), sds((slots, w, HQ, dr), jnp.bfloat16),
        sds((blocks, layers, 1, t, r), jnp.bfloat16),
        sds((blocks, layers, 1, t, dr), jnp.bfloat16),
        sds((slots, width), jnp.int32), sds((slots,), jnp.int32),
        sds((slots,), jnp.bool_), sds((slots,), jnp.int32), sds((), jnp.int32),
        sds((), jnp.int32),
    )


def test_the_latent_kernels_run_is_sized_by_the_latent_caches_bytes():
    """What ``_run_entries`` gives at the two cells' shapes: 1,280 KiB of
    1,280 B rows = 1,024 tokens a run, 16 blocks of 64 and 64 blocks of 16;
    a narrow table bounds it, wider rows shorten it, and the dense kernel's
    rule is not asked."""
    from nats_llm_studio_tpu.ops.mla_attention import _run_entries

    row = (512 + 128) * 2
    got = {cell: _run_entries(t, width, row) for cell, (_, t, width, _) in MLA_CELLS.items()}
    assert got == {"xing29b.answer_closed": 64, "kanana2.longctx_closed": 16}
    assert _run_entries(64, 8, row) == 8
    assert _run_entries(64, 512, 2 * row) == 8
    assert _run_entries(256, 128, 8 * row) == 1


def test_moe_hit_experts_at_the_benchmark_cells_shapes(one_chip, no_cache):
    """The expert layer of a decode step of ``xing29b.answer_closed``: 8 rows
    x top-4 = 32 places over the WHOLE stacks of 6 expert layers x 64 experts
    of [3584, 1024] bf16 (8.5 GB, indexed in place by the scalar-prefetched
    layer and hit list), tiles of 512 columns of ``f``: three double-buffered
    3.7 MB tiles are 22 MB of VMEM, over the 16 MB a kernel gets unasked."""
    from nats_llm_studio_tpu.ops.moe_experts import _f_tile, moe_hit_experts

    layers, e, d, f, rows, places = 6, 64, 3584, 1024, SLOTS, 32
    assert _f_tile(d, f, 2) == 512
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    compiled = _compile(
        moe_hit_experts,
        sds((rows, d), jnp.bfloat16), sds((places, rows), jnp.float32),
        sds((places,), jnp.int32), sds((), jnp.int32), sds((), jnp.int32),
        sds((layers, e, d, f), jnp.bfloat16), sds((layers, e, d, f), jnp.bfloat16),
        sds((layers, e, f, d), jnp.bfloat16), sds((rows, d), jnp.float32),
    )
    # the stacks are read where they lie: no copy of a layer's slice
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("rows", [SLOTS, SLOTS * SPEC_W, 4 * CHUNK],
                         ids=["decode", "spec_verify", "chunk_group_of_4"])
def test_hc_sinkhorn_at_the_benchmark_cells_shapes(one_chip, no_cache, rows):
    """The mixing map of the four-stream residual, 20 rounds in one kernel:
    [4, 4, rows] float32 on one lane tile."""
    from nats_llm_studio_tpu.ops.sinkhorn import sinkhorn_rounds

    _compile(lambda res: sinkhorn_rounds(res, 20, 1e-6),
             jax.ShapeDtypeStruct((4, 4, rows), jnp.float32, sharding=one_chip))


@pytest.mark.parametrize("width", [1, 4], ids=["prefill1", "chunk_group_of_4"])
def test_a_prefill_chunk_at_the_benchmark_cells_shapes_copies_no_expert_stack(
        one_chip, no_cache, width):
    """A chunk of 256 tokens x ``width`` prompts of ``xing29b.answer_closed``
    through the whole cut model (1 dense + 6 expert layers, 64 experts of
    [3584, 1024] x 3 in bf16: 8.5 GB of stacks, 1.4 GB a layer): the expert
    layers take the grouped form, one ``moe_grouped_experts`` kernel in the
    scan over the layers, and the stacks are read where they lie. A scan's
    slice of a stack handed to the kernel would show as 470 MB of ``temp`` a
    stack; what is there is the chunk's activations."""
    import json
    from pathlib import Path

    from benchmark import run
    from nats_llm_studio_tpu.models import llama, mla_moe

    root = Path(__file__).resolve().parents[1]
    ref = run.load_module(root / "benchmark/references/mla_moe_mhc.py")
    conf = json.loads((root / "benchmark/configs/xing4.0-29b-a4b.json").read_text())
    cfg = ref.model_config(conf, SEQ)
    moe = jax.eval_shape(lambda: mla_moe.init_params(cfg, jax.random.PRNGKey(0)))
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    caches = [jax.ShapeDtypeStruct((width, cfg.n_layers, h, SEQ, w), jnp.bfloat16,
                                   sharding=one_chip) for h, w in cfg.kv_cache_dims()]
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"   # the kernel itself, not the interpreter
    try:
        compiled = _compile(
            lambda params, tokens, k, v, start, last: llama.forward(
                params, cfg, tokens, k, v, start, logit_positions=last,
                uniform_start=True, attn_window=2 * CHUNK),
            jax.tree.map(sds, moe), ints(width, CHUNK), *caches, ints(width), ints(width))
    finally:
        jax.default_backend = orig
    text = compiled.as_text()
    assert text.count("moe_grouped_experts") >= 1 and "moe_hit_experts" not in text
    stack_slice = cfg.n_experts * cfg.d_model * cfg.moe_d_ff * 2
    assert compiled.memory_analysis().temp_size_in_bytes < stack_slice


# -- a prefill chunk of the dense family at granite8b.chat_closed's shapes --


_NO_OPERATION = {"get-tuple-element", "bitcast", "parameter", "constant", "tuple"}


def _scoped_operations(text: str, scope: str) -> dict[str, list[tuple[str, str]]]:
    """{computation: [(opcode, line)]} of a compiled program's device
    operations whose ``op_name`` holds ``scope``: every instruction outside
    the fused computations that is not a tuple's plumbing, a bitcast, a
    parameter or a constant (a fusion counts once, its body not at all)."""
    found: dict[str, list[tuple[str, str]]] = {}
    name = None
    for ln in text.splitlines():
        head = ln.split(" ", 2)
        if ln.endswith("{") and not ln.startswith(" ") and len(head) > 1:
            name = head[1] if head[0] == "ENTRY" else head[0]
        elif name is not None and ln.startswith("  ") and "fused_computation" not in name:
            m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = .*?\s([a-z][a-z\-]*)\(", ln)
            if m and m.group(1) not in _NO_OPERATION and scope in ln.split("op_name=", 1)[-1]:
                found.setdefault(name, []).append((m.group(1), ln.strip()))
    return found


def _whole_array_copies(text: str, shape: str) -> list[str]:
    """The instructions of a compiled program that write a relayouted copy of
    a whole array of ``shape`` ("bf16[1,40,8,2048,128]") to memory: a ``copy``
    or ``copy-start`` outside any fusion, or a fusion with that result whose
    body holds one. A copy fused into a smaller result (the layer's
    ``dynamic-slice`` of the group path) moves the slice only and is not
    counted."""
    bodies: dict[str, list[str]] = {}
    name = None
    for ln in text.splitlines():
        head = ln.split(" ", 2)
        if ln.endswith("{") and not ln.startswith(" ") and len(head) > 1:
            name = head[1] if head[0] == "ENTRY" else head[0]
            bodies[name] = []
        elif name is not None and ln.startswith("  "):
            bodies[name].append(ln.strip())

    def copies_whole(ln: str) -> bool:
        return (" copy(" in ln or "copy-start(" in ln) and shape in ln.split(" copy", 1)[0]

    found = []
    for name, lines in bodies.items():
        if name.startswith("%fused_computation"):
            continue
        for ln in lines:
            result = ln.split(" fusion(", 1)[0]
            if copies_whole(ln):
                found.append(f"{name}: {ln[:200]}")
            elif " fusion(" in ln and shape in result and "calls=" in ln:
                callee = ln.split("calls=", 1)[1].split(",", 1)[0].split(" ", 1)[0]
                if any(copies_whole(inner) for inner in bodies.get(callee, ())):
                    found.append(f"{name}: {ln[:200]}")
    return found


def test_whole_array_copies_tells_a_fused_slice_from_a_relayout():
    text = """HloModule m
%fused_computation.1 (p: bf16[4,8]) -> bf16[4,8] {
  %p = bf16[4,8]{1,0} parameter(0)
  ROOT %copy.1 = bf16[4,8]{0,1} copy(%p)
}

%fused_computation.2 (p: bf16[4,8]) -> bf16[1,8] {
  %p = bf16[4,8]{1,0} parameter(0)
  %fusion.9 = bf16[4,8]{0,1} fusion(%p), kind=kLoop, calls=%fused_computation.1
  ROOT %ds = bf16[1,8]{1,0} dynamic-slice(%fusion.9), dynamic_slice_sizes={1,8}
}

%body (t: (bf16[4,8])) -> (bf16[4,8]) {
  %g = bf16[4,8]{1,0} get-tuple-element(%t), index=0
  %fusion.2 = bf16[1,8]{1,0} fusion(%g), kind=kLoop, calls=%fused_computation.2
  %copy.7 = bf16[4,8]{0,1} copy(%g)
  %fusion.3 = bf16[4,8]{0,1} fusion(%g), kind=kLoop, calls=%fused_computation.1
}

ENTRY %main (a: bf16[4,8]) -> bf16[4,8] {
  %a = bf16[4,8]{1,0} parameter(0)
  %copy-start = (bf16[4,8]{0,1}, bf16[4,8]{1,0}, u32[]) copy-start(%a)
}
"""
    found = _whole_array_copies(text, "bf16[4,8]")
    assert [f.split(" = ")[0] for f in found] == [
        "%body: %copy.7", "%body: %fusion.3", "%main: %copy-start"], found


def _served_tree(shapes: dict, wquant: str, prefix: str = "") -> dict:
    """``program_param_shapes``' tree as the loader would rest it under
    WQUANT ``wquant``: int8 codes with a scale a column, packed int4 codes with
    a scale and a zero point a group of 128 rows, or ("none") as it is."""
    from nats_llm_studio_tpu.ops.wquant import QTensor, QTensor4, quantizable

    out = {}
    for k, v in shapes.items():
        if isinstance(v, dict):
            out[k] = _served_tree(v, wquant, f"{prefix}{k}.")
            continue
        if wquant == "none" or not quantizable(prefix + k):
            out[k] = v
            continue
        lead, (rows, cols) = v.shape[:-2], v.shape[-2:]
        sds = lambda dt, *tail: jax.ShapeDtypeStruct(lead + tail, dt)  # noqa: E731
        if wquant == "int8":
            out[k] = QTensor(q=sds(jnp.int8, rows, cols), s=sds(jnp.float32, 1, cols))
        else:
            out[k] = QTensor4(q=sds(jnp.uint8, rows // 2, cols), s=sds(jnp.float32, rows // 128, cols),
                              z=sds(jnp.float32, rows // 128, cols), group=128)
    return out


@pytest.fixture(scope="module")
def dense_cfg():
    """``benchmark/configs/granite-3.1-8b.json`` as both Granite-8B cells serve
    it: MAX_SEQ_LEN 2048, the flash kernels on."""
    import json
    from pathlib import Path

    from benchmark import run

    root = Path(__file__).resolve().parents[1]
    ref = run.load_module(root / "benchmark/references/granite_dense.py")
    conf = json.loads((root / "benchmark/configs/granite-3.1-8b.json").read_text())
    return ref.model_config(conf, 2048).with_(use_flash_attention=True)


@pytest.fixture(scope="module")
def dense_cell(dense_cfg):
    """(cfg, the served tree's shapes) of the Granite-8B cells: int8 weights
    (WQUANT=int8)."""
    from benchmark.lib.weights import program_param_shapes

    return dense_cfg, _served_tree(program_param_shapes(dense_cfg), "int8")


@pytest.mark.parametrize("width,window,kv", [
    (1, 512, "bf16"), (1, 1024, "bf16"), (1, 2048, "bf16"), (1, 512, "int8"), (4, 512, "bf16"),
], ids=["prefill1-512", "prefill1-1024", "prefill1-2048", "prefill1-512-int8kv",
        "chunk_group_of_4-512"])
def test_a_prefill_chunk_of_the_dense_family_copies_no_row_cache(one_chip, no_cache, dense_cell,
                                                                 width, window, kv):
    """A chunk of 256 tokens x ``width`` prompts of ``granite8b.chat_closed``
    (40 layers, 8 kv heads of 128, row caches [width, 40, 8, 2048, 128]: 168 MB
    each a row) through ``prefill1`` / ``prefill_chunk_group`` as
    ``serve/programs.py`` builds them, over the ladder of attention windows:
    the chunk kernel is in the layer scan, the donated pair is the results'
    own buffers, and no relayouted copy of a whole row cache is written
    anywhere: not in the while body (at width 1 the parent's carry was
    ``{3,4,2,1,0}`` and ``%copy.17`` / ``%copy.18`` put it back to
    ``{4,3,2,1,0}`` in every layer of a continuation chunk, 41 ms of its
    75 ms launch on the chip), not on entry and not at the exit. From two rows up the carry is
    the default layout unasked; that case guards the group path."""
    from nats_llm_studio_tpu.engine.sampling import sample_rows
    from nats_llm_studio_tpu.serve.programs import build_programs

    cfg, shapes = dense_cell
    seq = cfg.max_seq_len
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    row = (width, cfg.n_layers, cfg.n_kv_heads, seq, cfg.head_dim)
    if kv == "int8":
        cache = KVQ(q=jax.ShapeDtypeStruct(row, jnp.int8, sharding=one_chip),
                    s=jax.ShapeDtypeStruct(row[:-1], jnp.float32, sharding=one_chip))
        codes, code_bytes = f"s8[{','.join(map(str, row))}]", 1
    else:
        cache = jax.ShapeDtypeStruct(row, jnp.bfloat16, sharding=one_chip)
        codes, code_bytes = f"bf16[{','.join(map(str, row))}]", 2
    table = build_programs(cfg, None, max_seq=seq, paged=True, kv_block_tokens=T,
                           sample_rows=sample_rows)
    program = table["prefill1" if width == 1 else "prefill_chunk_group"]
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"   # the kernels themselves, not the interpreter
    try:
        compiled = program.lower(jax.tree.map(sds, shapes), ints(width, CHUNK), cache, cache,
                                 ints(width), ints(width), window).compile()
    finally:
        jax.default_backend = orig
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "flash_attention_chunk" in text
    copies = _whole_array_copies(text, codes)
    assert not copies, copies
    pair = 2 * int(np.prod(row)) * code_bytes
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= pair           # the donated pair, no entry copy
    assert ma.temp_size_in_bytes < pair // 2 // 8   # and no second row cache among the temporaries


def _unfused_results(text: str, *shapes: str) -> list[str]:
    """The device operations of a compiled program, outside its fused
    computations, whose result is one of ``shapes`` ("s8[1,4096,1024]")."""
    return [ln[:200] for ops in _scoped_operations(text, "").values() for _, ln in ops
            if ln.split(" = ", 1)[1].lstrip("(").startswith(shapes)]


_PREFETCH = ("copy-start", "copy-done", "slice-start", "slice-done")


def _moved_from_rest(text: str, *shapes: str) -> list[str]:
    """Of ``_unfused_results``, what writes an array of ``shapes`` anew: every
    such operation but the compiler's own prefetch of an array into fast
    memory as it rests (``copy-start`` / ``slice-start`` and their ``-done``
    into ``S(1)`` in the row-major layout, and the ``ConcatBitcast`` that joins
    a prefetch made in slices): that one overlaps the operations before it and
    reads HBM once, where a slice fusion or a ``{1,2,0}`` copy stands in the
    step's way and writes what it read."""
    def prefetch(ln: str) -> bool:
        result = ln.split(" = ", 1)[1].lstrip("(").split(" ", 1)[0]
        return "{2,1,0" in result and ("ConcatBitcast" in ln or any(f" {p}(" in ln for p in _PREFETCH))
    return [ln for ln in _unfused_results(text, *shapes) if not prefetch(ln)]


def test_moved_from_rest_tells_a_prefetch_from_a_relayout():
    text = """HloModule m
%body (t: (bf16[6,64,64])) -> (bf16[6,64,64]) {
  %g = bf16[6,64,64]{2,1,0} get-tuple-element(%t), index=0
  %constant_dynamic-slice_fusion.9 = bf16[1,64,64]{1,2,0:T(8,128)(2,1)S(1)} fusion(%g), kind=kLoop, calls=%fused_computation.1
  %slice-start = ((bf16[6,64,64]{2,1,0}), bf16[1,64,64]{2,1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) slice-start(%g), slice={[0:1], [0:64], [0:64]}
  %slice-done = bf16[1,64,64]{2,1,0:T(8,128)(2,1)S(1)} slice-done(%slice-start)
  %copy-start.1 = (bf16[1,64,64]{1,2,0:T(8,128)(2,1)S(1)}, bf16[1,64,64]{2,1,0}, u32[]{:S(2)}) copy-start(%slice-done)
  %copy-done.1 = bf16[1,64,64]{1,2,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.1)
  %custom-call.4 = bf16[6,64,64]{2,1,0:T(8,128)(2,1)S(1)} custom-call(%slice-done), custom_call_target="ConcatBitcast"
}

ENTRY %main (a: bf16[6,64,64]) -> bf16[6,64,64] {
  %a = bf16[6,64,64]{2,1,0} parameter(0)
  %copy.281 = bf16[6,64,64]{1,2,0:T(8,128)(2,1)} copy(%a)
  %copy-start = (bf16[6,64,64]{2,1,0:T(8,128)(2,1)S(1)}, bf16[6,64,64]{2,1,0}, u32[]{:S(2)}) copy-start(%a)
}
"""
    moved = _moved_from_rest(text, "bf16[6,64,64]", "bf16[1,64,64]")
    assert [ln.split(" = ")[0] for ln in moved] == [
        "%constant_dynamic-slice_fusion.9", "%copy-start.1", "%copy-done.1", "%copy.281"], moved


@pytest.fixture(scope="module")
def mla_cell():
    """(cfg, the served tree's shapes, block tokens, slots, context) of
    ``benchmark/configs/xing4.0-29b-a4b.json``: the latent form with a query
    latent (``q_lora_rank``: ``w_dq``, then ``w_uq`` cut into heads)."""
    import json
    from pathlib import Path

    from benchmark import run

    root = Path(__file__).resolve().parents[1]
    ref = run.load_module(root / "benchmark/references/mla_moe_mhc.py")
    conf = json.loads((root / "benchmark/configs/xing4.0-29b-a4b.json").read_text())
    env = conf["serving"]["env"]
    seq = int(env["MAX_SEQ_LEN"])
    cfg = ref.model_config(conf, seq)
    return cfg, ref.param_shapes(cfg), int(env["KV_BLOCK_TOKENS"]), int(env["MAX_BATCH_SLOTS"]), seq


# family -> (its cell's fixture, (cfg, shapes, block tokens, slots, context) of
# what the fixture gives, the blocks its prefix cache adds to the pool)
_QKV_CELLS = {
    "lightning": ("sala_cell", lambda c: c, 0),
    "window": ("swa_cell", lambda c: (c[0].with_(use_flash_attention=True), *c[1:], SWA_SLOTS, SWA_SEQ), 0),
    "latent-plain": ("mla_plain_cell", lambda c: c, 64),
    "latent-q-lora": ("mla_cell", lambda c: c, 64),
    "gated-delta": ("gdn_cell", lambda c: c, 0),
    "state-space": ("ssm_cell", lambda c: (*c, T, SSM_SLOTS, SEQ), 0),
}
_QKV_STACKS = {"wq", "wk", "wv", "w_uq"}
_HLO_TYPES = {"bfloat16": "bf16", "int8": "s8", "uint8": "u8"}


def _qkv_stacks(params) -> list[tuple[str, tuple[int, ...]]]:
    """(HLO element type, shape) of every q / k / v projection stack [layers,
    rows, cols] in a tree of shapes (a quantised leaf's codes; never its
    scales)."""
    found = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = {getattr(k, "key", None) for k in path}
        if keys & _QKV_STACKS and leaf.ndim == 3 and leaf.dtype.name in _HLO_TYPES:
            found.add((_HLO_TYPES[leaf.dtype.name], tuple(leaf.shape)))
    return sorted(found)


@pytest.mark.parametrize("family,program,wquant", [
    ("dense", "decode_pallas", "int8"), ("dense", "spec_verify_pallas", "int8"),
    ("dense", "prefill1", "int8"), ("dense", "prefill_chunk_group", "int8"),
    ("dense", "admit_fused_paged", "int8"), ("dense", "decode_pallas", "none"),
    ("dense", "decode_pallas", "int4"),
    *[(family, program, "none") for family in _QKV_CELLS for program in ("decode_pallas", "prefill1")],
], ids=["burst", "spec_verify", "prefill1", "chunk_group_of_4", "admit_fused_paged",
        "burst-bf16-of-16-layers-reads-its-slices-at-rest-too",
        "burst-int4-unpacks-a-bf16-slice-a-layer-as-the-parent-did",
        *[f"{family}-{name}" for family in _QKV_CELLS for name in ("burst", "prefill1")]])
def test_the_qkv_products_read_their_slice_out_of_the_stack_at_rest(
        request, one_chip, no_cache, family, program, wquant):
    """Every family's programs as ``serve/programs.py`` builds them, at its
    cell's shapes (the burst is 8 steps, the dense verify 7 wide, the chunks
    256 tokens, the dense admit a bucket of 256): ``wq`` / ``wk`` / ``wv`` (and
    the latent form's ``w_uq``) are read where they rest, by the product
    itself, as the MLP's stacks and ``wo`` are. ``ops/wquant.py flat_rows``
    keeps the cut into heads out of the products; with it folded in the
    compiler bitcast each layer's slice to [d_model, heads, head_dim], could
    then no longer fuse the slice as the product's operand, and wanted the
    contraction axis minor. So the dense burst (the parent of PR 52) began with
    ``%copy.86`` ``s8[40,4096,4096]{1,2,0}`` and ``.85`` / ``.87``
    ``s8[40,4096,1024]{1,2,0}`` (``temp_size_in_bytes`` 1,015,474,176), every
    layer of every step ran ``%constant_dynamic-slice_fusion.6/.7/.8`` over
    those copies, and the one-row programs a slice fusion and a ``{1,2,0}`` copy
    a stack a layer; the parent of PR 53 held the same in every other family
    (the Lightning burst three ``bf16[1,4096,4096]{1,2,0}`` a layer over three
    copied ``bf16[6,4096,4096]`` stacks, 620 MB of temporaries; the slices each
    case found are in ``CHANGES.md``, PR 53). None of that is left: no copy of
    a whole stack and no operation outside a fusion that yields one layer's
    slice of one. What is NOT held: the latent form's ``w_ukv``, a weight that
    is itself cut into heads, whose slice a layer is still copied (PERF.md
    section 7).

    The dense burst over the other trees the worker rests, ids as found: bf16
    stacks (16 of the 40 layers, what fits 16 GB) compiled to the parent's
    form and now to this one, 808,554,496 bytes of temporaries to 3 MB; packed
    int4 stacks never took the parent's form (``_mm4`` unpacks each layer's
    slice into bf16 [2048, 2, cols] and copies it, on both sides of this
    change: what is held is that the stacks themselves are copied nowhere and
    that the unpacked slices are as many as they were)."""
    from benchmark.lib.weights import program_param_shapes
    from nats_llm_studio_tpu.engine.sampling import sample_rows
    from nats_llm_studio_tpu.models import llama
    from nats_llm_studio_tpu.ops.kvcache import WithState
    from nats_llm_studio_tpu.serve.programs import build_programs

    if family == "dense":
        cfg = request.getfixturevalue("dense_cfg")
        cfg = cfg.with_(n_layers=16) if wquant == "none" else cfg
        shapes, t, slots, seq, spare = (_served_tree(program_param_shapes(cfg), wquant), T, SLOTS,
                                        cfg.max_seq_len, 64)
        window = 4 * CHUNK
    else:
        fixture, cell, spare = _QKV_CELLS[family]
        cfg, shapes, t, slots, seq = cell(request.getfixturevalue(fixture))
        window = seq
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(sds, shapes)
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    floats = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)  # noqa: E731

    def pools():
        pair = [jax.ShapeDtypeStruct((slots * (seq // t) + spare + 1, cfg.n_kv_layers, h, t, w),
                                     jnp.bfloat16, sharding=one_chip) for h, w in cfg.kv_cache_dims()]
        if not cfg.slot_state:
            return pair
        axes = []   # static: taken as the state is made, not as shapes

        def states():
            made = llama.family_module(cfg).make_state(cfg, slots)
            axes.extend(ax for _, ax in made)
            return [st for st, _ in made]

        state = jax.tree.map(sds, jax.eval_shape(states))
        return [WithState(p, st, ax) for p, st, ax in zip(pair, state, axes)]

    rows = lambda w: jax.tree.map(sds, jax.eval_shape(  # noqa: E731
        lambda: llama.make_cache(cfg, w, seq, "bfloat16")))
    slot = (ints(slots), ints(slots), floats(slots), ints(slots), floats(slots))  # seeds ... topp
    args = {
        "decode_pallas": lambda: (params, ints(slots), *pools(), ints(slots, seq // t), ints(slots),
                                  *slot, 8),
        "spec_verify_pallas": lambda: (params, ints(slots), *pools(), ints(slots, seq // t),
                                       ints(slots), ints(slots, SPEC_W - 1), ints(slots), *slot),
        "prefill1": lambda: (params, ints(1, CHUNK), *rows(1), ints(1), ints(1), window),
        "prefill_chunk_group": lambda: (params, ints(4, CHUNK), *rows(4), ints(4), ints(4), window),
        "admit_fused_paged": lambda: (params, *pools(), ints(slots), ints(1, CHUNK), ints(),
                                      ints(CHUNK // t), ints(), ints(), floats(), ints(), floats()),
    }[program]()
    table = build_programs(cfg, None, max_seq=seq, paged=True, kv_block_tokens=t,
                           sample_rows=sample_rows)
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"   # the kernels themselves, not the interpreter
    try:
        compiled = table[program].lower(*args).compile()
    finally:
        jax.default_backend = orig
    text = compiled.as_text()
    assert "tpu_custom_call" in text or (family, program) == ("state-space", "prefill1")  # no kernel in it
    stacks = _qkv_stacks(shapes)
    assert stacks
    name = lambda code, shape: f"{code}[{','.join(map(str, shape))}]"  # noqa: E731
    held = [name(code, shape) for code, shape in stacks]
    if wquant != "int4":   # and a layer's slice of each (int4 unpacks its slices: below)
        held += [name(code, (1,) + shape[1:]) for code, shape in stacks]
    moved = _moved_from_rest(text, *held)
    assert not moved, moved
    if wquant == "int4":
        kv_cols = cfg.n_kv_heads * cfg.head_dim
        unpacked = _unfused_results(text, f"bf16[{cfg.d_model // 2},2,{kv_cols}]")
        assert len(unpacked) == 4, unpacked   # wk and wv: the unpacking fusion and its copy
        return
    ma = compiled.memory_analysis()
    if family == "dense" and program == "decode_pallas":
        assert ma.temp_size_in_bytes < 64 << 20
    print(f"\n{family} {program}: temp {ma.temp_size_in_bytes / 1e6:.0f} MB")


# -- the state-space / attention hybrid family at granite4hmicro.chat32_closed's shapes --


@pytest.fixture(scope="module")
def ssm_cell():
    """(cfg, the served tree's shapes) of ``benchmark/configs/granite-4.0-h-micro.json``."""
    import json
    from pathlib import Path

    from benchmark import run

    root = Path(__file__).resolve().parents[1]
    ref = run.load_module(root / "benchmark/references/ssm_hybrid.py")
    conf = json.loads((root / "benchmark/configs/granite-4.0-h-micro.json").read_text())
    cfg = ref.model_config(conf, SEQ)
    return cfg, ref.param_shapes(cfg)


SSM_SLOTS = 32


def _ssm_pools(cfg, sharding):
    """The cell's pools: 32 x 256 + 1 blocks of 4 layers of packed rows, each
    with the 32 slots' state beside it."""
    from nats_llm_studio_tpu.models import ssm_hybrid
    from nats_llm_studio_tpu.ops.kvcache import WithState

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    (h, w), _ = cfg.kv_cache_dims()
    nb = SSM_SLOTS * (SEQ // T) + 1
    (tail, seen), (plane,) = ssm_hybrid.state_shapes(cfg, SSM_SLOTS)
    kv = lambda: sds((nb, cfg.n_kv_layers, h, T, w), jnp.bfloat16)  # noqa: E731
    return (WithState(kv(), (sds(tail, jnp.bfloat16), sds(seen, jnp.int32)), ssm_hybrid.K_AXES),
            WithState(kv(), (sds(plane, jnp.float32),), ssm_hybrid.V_AXES))


def _tail_tiles(text: str, tails) -> set[str]:
    """The tiles the compiler gave a layer's convolution tail [K, slots, C]
    wherever the compiled text names it."""
    _, k, slots, c = tails.shape
    return set(re.findall(rf"bf16\[{k},{slots},{c}\]\{{[^}}]*?:(T\(\d+,128\)\(2,1\))", text))


def test_ssm_state_step_at_the_benchmark_cells_shapes(one_chip, no_cache, ssm_cell):
    """One layer's step over the state pool [32, 36, 32, 128, 128] f32
    (2.4 GB), the live slots a traced mask: Mosaic tiles it, the list rides
    in as scalars, and the pool is the result's own buffer (the alias holds:
    no second pool among the temporaries)."""
    from nats_llm_studio_tpu.ops import ssm_scan

    cfg, _ = ssm_cell
    _, vp = _ssm_pools(cfg, one_chip)
    pool = vp.st[0]
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
    h, p, n = cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_d_state
    compiled = jax.jit(
        lambda pool, layer, mask, decay, dtx, bm, cm: ssm_scan.ssm_state_step(
            pool, layer, ssm_scan.live_slots(mask), decay, dtx, bm, cm),
        donate_argnums=(0,)).lower(
        pool, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((SSM_SLOTS,), jnp.bool_, sharding=one_chip),
        f32(SSM_SLOTS, h), f32(SSM_SLOTS, h, p), f32(SSM_SLOTS, n), f32(SSM_SLOTS, n)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssm_state_step" in text
    ma = compiled.memory_analysis()
    pool_bytes = int(np.prod(pool.shape)) * 4
    assert ma.alias_size_in_bytes >= pool_bytes and ma.temp_size_in_bytes < pool_bytes // 8


@pytest.mark.parametrize("program", ["decode_pallas", "decode_pallas_ext"],
                         ids=["the burst", "the single step"])
def test_a_decode_launch_of_the_state_space_family_copies_no_pool(one_chip, no_cache, ssm_cell,
                                                                  program):
    """The family's two decode programs as ``serve/programs.py`` builds them
    (eight steps and their sampling; one masked step with its
    log-probabilities), all 40 layers over the cell's pools, donated: one
    scan over the 4 periods and one over each run of layers inside it
    (compiled in seconds, not 40 unrolled layers), both kernels in it under
    their names, the pools aliased onto the results, and no ``copy`` of the
    float32 state pool, of the convolution tails or of a KV pool anywhere in
    the program. The live slots are listed from the block table, an argument
    of the program like the positions: launches with other slots live, or
    none, are this one program."""
    from nats_llm_studio_tpu.engine.sampling import sample_rows
    from nats_llm_studio_tpu.serve.programs import build_programs

    cfg, shapes = ssm_cell
    kp, vp = _ssm_pools(cfg, one_chip)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    row = lambda dt, *more: jax.ShapeDtypeStruct(  # noqa: E731
        (SSM_SLOTS,) + more, dt, sharding=one_chip)
    ints, floats = row(jnp.int32), row(jnp.float32)
    table = build_programs(cfg, None, max_seq=SEQ, paged=True, kv_block_tokens=T,
                           sample_rows=sample_rows)
    # (params, tok, KP, VP, tbl, pos, seeds, steps, temp, topk, topp, n | mask)
    last = 8 if program == "decode_pallas" else row(jnp.bool_, cfg.vocab_size)
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"   # the kernels themselves, not the interpreter
    try:
        lowered = table[program].lower(
            jax.tree.map(sds, shapes), ints, kp, vp, row(jnp.int32, SEQ // T), ints, ints, ints,
            floats, ints, floats, last)
        compiled = lowered.compile()
    finally:
        jax.default_backend = orig
    tbl = lowered.args_info[0][4]   # traced, not static: the list is data
    assert tbl.shape == (SSM_SLOTS, SEQ // T) and tbl.dtype == jnp.int32
    text = compiled.as_text()
    assert "ssm_state_step" in text and "paged_decode_attention" in text
    state, kv, tails = vp.st[0], kp.kv, kp.st[0]
    pools = (f"f32[{','.join(map(str, state.shape))}]", f"bf16[{','.join(map(str, kv.shape))}]",
             f"bf16[{','.join(map(str, tails.shape))}]")
    copies = [ln.strip()[:160] for ln in text.splitlines()
              if (" copy(" in ln or "copy-start(" in ln) and any(p in ln for p in pools)]
    assert not copies, copies
    # a tap a plane, the slots on the sublanes: whole 8-row bf16 tiles, where
    # [slots, K, C] gave T(4,128)(2,1) and two relayout copies a layer (PR 57)
    assert tails.shape == (cfg.n_ssm_layers, cfg.ssm_conv, SSM_SLOTS, cfg.ssm_conv_dim)
    assert _tail_tiles(text, tails) == {"T(8,128)(2,1)"}
    ma = compiled.memory_analysis()
    state_bytes = int(np.prod(state.shape)) * 4
    assert ma.alias_size_in_bytes >= state_bytes + 2 * int(np.prod(kv.shape)) * 2
    assert ma.temp_size_in_bytes < state_bytes // 8


def test_the_live_list_is_the_state_space_familys_alone():
    """The list is made from the block table inside ``models/ssm_hybrid.py``:
    the program table hands every family's decode forward the arguments it
    handed it before, and knows of no list. So the lowered text of a
    Granite-3.1 and of a Xing decode burst, and with it their compile-cache
    keys, are the parent's (compared by hash on this change's tree, PERF.md
    PR 36: a check that needs the parent's checkout)."""
    import inspect

    from nats_llm_studio_tpu.models import llama, mla_moe, ssm_hybrid
    from nats_llm_studio_tpu.serve import programs

    names = lambda fn: list(inspect.signature(fn).parameters)  # noqa: E731
    family = ["params", "cfg", "tokens", "k_pool", "v_pool", "tbl", "start_pos", "mesh"]
    assert names(llama.forward_decode_paged) == family + ["moe_stats"]
    assert names(mla_moe.forward_decode_paged) == family
    assert names(ssm_hybrid.forward_decode_paged) == family
    src = inspect.getsource(programs)
    assert "ssm_scan" not in src and "live_slots" not in src and "table_rows_in_use" not in src


# -- the window / full attention family at lagunaxs2.code_closed's shapes --------


SWA_SLOTS, SWA_SEQ = 16, 18432


@pytest.fixture(scope="module")
def swa_cell():
    """(cfg, the served tree's shapes) of ``benchmark/configs/laguna-xs.2.json``."""
    import json
    from pathlib import Path

    from benchmark import run

    root = Path(__file__).resolve().parents[1]
    ref = run.load_module(root / "benchmark/references/swa_gated_moe.py")
    conf = json.loads((root / "benchmark/configs/laguna-xs.2.json").read_text())
    cfg = ref.model_config(conf, SWA_SEQ)
    return cfg, ref.param_shapes(cfg), int(conf["serving"]["env"]["KV_BLOCK_TOKENS"])


def _swa_pools(cfg, sharding, t):
    """The cell's pools: 16 x 18,432 / t + 1 blocks of the 2 full layers, each
    with the 16 slots' rings of the 3 window layers beside it."""
    from nats_llm_studio_tpu.models import swa_moe
    from nats_llm_studio_tpu.ops.kvcache import WithState

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)

    nb = SWA_SLOTS * (SWA_SEQ // t) + 1
    return tuple(WithState(sds((nb, cfg.n_kv_layers, cfg.n_kv_heads, t, cfg.head_dim)),
                           (sds(swa_moe.ring_shape(cfg, SWA_SLOTS)),), swa_moe.RING_AXES)
                 for _ in range(2))


def test_the_ring_kernel_at_the_benchmark_cells_shapes(one_chip, no_cache, swa_cell):
    """16 slots, 64 query heads over 8 kv heads (group 8), a ring of 512 keys
    of 128 lanes, bf16, the layer a traced scalar: Mosaic tiles it and the
    call carries its own name."""
    from nats_llm_studio_tpu.ops.paged_attention import (
        window_decode_attention, window_decode_eligible)

    cfg, _, _ = swa_cell
    assert window_decode_eligible(cfg.window, cfg.head_dim, 2)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    ring = sds((cfg.n_win_layers, SWA_SLOTS, cfg.n_kv_heads, cfg.window, cfg.head_dim), jnp.bfloat16)
    compiled = _compile(
        lambda q, rk, rv, pos, layer: window_decode_attention(q, rk, rv, pos, layer, SCALE),
        sds((SWA_SLOTS, 1, cfg.win_n_heads, cfg.head_dim), jnp.bfloat16), ring, ring,
        sds((SWA_SLOTS,), jnp.int32), sds((), jnp.int32))
    assert "window_decode_attention" in compiled.as_text()


@pytest.fixture(scope="module")
def swa_decode_launch(one_chip, no_cache, swa_cell):
    """The window family's decode programs as ``serve/programs.py`` builds
    them, compiled once a program at the cell's shapes: all 5 layers over
    the cell's pools and rings, donated. ``(compiled, kp)`` by program name."""
    from functools import cache

    from nats_llm_studio_tpu.engine.sampling import sample_rows
    from nats_llm_studio_tpu.serve.programs import build_programs

    cfg, shapes, t = swa_cell
    kp, vp = _swa_pools(cfg, one_chip, t)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    row = lambda dt, *more: jax.ShapeDtypeStruct(  # noqa: E731
        (SWA_SLOTS,) + more, dt, sharding=one_chip)
    ints, floats = row(jnp.int32), row(jnp.float32)
    table = build_programs(cfg, None, max_seq=SWA_SEQ, paged=True, kv_block_tokens=t,
                           sample_rows=sample_rows)

    @cache
    def launch(program):
        last = 8 if program == "decode_pallas" else row(jnp.bool_, cfg.vocab_size)
        orig = jax.default_backend
        jax.default_backend = lambda: "tpu"   # the kernels themselves, not the interpreter
        try:
            return table[program].lower(
                jax.tree.map(sds, shapes), ints, kp, vp, row(jnp.int32, SWA_SEQ // t), ints,
                ints, ints, floats, ints, floats, last).compile(), kp
        finally:
            jax.default_backend = orig

    return launch


@pytest.mark.parametrize("program", ["decode_pallas", "decode_pallas_ext"],
                         ids=["the burst", "the single step"])
def test_a_decode_launch_of_the_window_family_copies_no_pool_and_no_ring(
        swa_decode_launch, program):
    """The family's two decode programs as ``serve/programs.py`` builds them,
    all 5 layers over the cell's pools and rings, donated: the slot table of
    16 x 1,152 entries (72 KiB) fits the kernel's scalar memory, both
    attention kernels and the hit-list expert kernel are in it under their
    names, the pools and the rings are aliased onto the results, and the
    program holds no ``copy`` and no ``dynamic-update-slice`` of a whole pool
    or ring (a row's key goes in by a scatter of one row)."""
    compiled, kp = swa_decode_launch(program)
    text = compiled.as_text()
    for name in ("window_decode_attention", "paged_decode_attention", "moe_hit_experts"):
        assert name in text, name
    kv, ring = kp.kv, kp.st[0]
    whole = (f"bf16[{','.join(map(str, kv.shape))}]", f"bf16[{','.join(map(str, ring.shape))}]")
    moved = [ln.strip()[:160] for ln in text.splitlines()
             if any(op in ln for op in (" copy(", "copy-start(", " dynamic-update-slice("))
             and any(ln.split("=", 1)[-1].strip().startswith(p) for p in whole)]
    assert not moved, moved
    ma = compiled.memory_analysis()
    held = 2 * (int(np.prod(kv.shape)) + int(np.prod(ring.shape))) * 2
    assert ma.alias_size_in_bytes >= held and ma.temp_size_in_bytes < held // 4


def _outside_conditionals(text: str) -> list[str]:
    """The instruction lines of an optimised HLO module that run on every
    pass through the program: those of no computation that a ``conditional``
    names as a branch, nor of one such a computation calls (a fusion's body,
    a nested loop's)."""
    import re

    def named(attributes: str, lines: list[str]) -> set[str]:
        found = re.findall(rf"(?:{attributes})=(\{{[^}}]*\}}|%[\w.\-]+)", " ".join(lines))
        return set(re.findall(r"%([\w.\-]+)", " ".join(found)))

    bodies: dict[str, list[str]] = {}
    name = None
    for ln in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", ln)
        if m and not ln.startswith(" "):
            name = m.group(1)
            bodies[name] = []
        elif name is not None and ln.startswith("}"):
            name = None
        elif name is not None:
            bodies[name].append(ln)
    branches = "true_computation|false_computation|branch_computations"
    under = set().union(*(named(branches, [ln for ln in lines if " conditional(" in ln])
                          for lines in bodies.values()))
    todo = list(under)
    while todo:
        for n in named(f"calls|to_apply|body|condition|{branches}", bodies.get(todo.pop(), [])):
            if n not in under:
                under.add(n)
                todo.append(n)
    return [ln for n, lines in bodies.items() if n not in under for ln in lines]


def test_outside_conditionals_reads_branches_and_what_they_call():
    text = """HloModule m

%fused_noise (p: u32[4,9]) -> f32[4,9] {
  %p = u32[4,9]{1,0} parameter(0)
  ROOT %l = f32[4,9]{1,0} log(%c)
}

%branch_a (q: f32[4,9]) -> s32[4] {
  %f = f32[4,9]{1,0} fusion(%q), kind=kLoop, calls=%fused_noise
}

%branch_b (q: f32[4,9]) -> s32[4] {
  %z = s32[4]{0} constant(0)
}

%fused_always (p: f32[4,9]) -> f32[4,9] {
  ROOT %e = f32[4,9]{1,0} exponential(%p)
}

ENTRY %main (x: f32[4,9]) -> s32[4] {
  %g = f32[4,9]{1,0} fusion(%x), kind=kLoop, calls=%fused_always
  ROOT %c = s32[4]{0} conditional(%p, %g, %g), true_computation=%branch_a, false_computation=%branch_b
}
"""
    outside = "\n".join(_outside_conditionals(text))
    assert "exponential(" in outside and "conditional(" in outside
    assert "log(" not in outside and "u32[4,9]" not in outside and "constant(0)" not in outside


def test_a_decode_burst_draws_whole_vocabulary_noise_only_inside_a_conditional(
        swa_decode_launch, swa_cell):
    """``lagunaxs2.code_closed``'s burst (16 rows x 100,352 logits): outside
    the sampler's conditionals no operation makes random bits (``u32``) or
    takes a logarithm over [rows, V], so a step whose rows are all greedy or
    restricted pays for no whole-vocabulary draw; and the conditionals are
    there (nothing turned them into selects of both branches)."""
    cfg, _, _ = swa_cell
    compiled, _ = swa_decode_launch("decode_pallas")
    text = compiled.as_text()
    whole = f"[{SWA_SLOTS},{cfg.vocab_size}]"

    def draws(ln: str) -> bool:
        return f"u32{whole}" in ln or (" log(" in ln and f"f32{whole}" in ln)

    assert text.count(" conditional(") >= 2
    assert any(map(draws, text.splitlines())), "the whole-vocabulary draw is in no branch either"
    always = [ln.strip()[:160] for ln in _outside_conditionals(text) if draws(ln)]
    assert not always, always


# -- the plain latent-attention form at kanana2.longctx_closed's shapes --------


@pytest.fixture(scope="module")
def mla_plain_cell():
    """(cfg, the served tree's shapes, block tokens, slots, context) of
    ``benchmark/configs/kanana-2-30b-a3b-instruct-2601.json``."""
    import json
    from pathlib import Path

    from benchmark import run

    root = Path(__file__).resolve().parents[1]
    ref = run.load_module(root / "benchmark/references/mla_moe_plain.py")
    conf = json.loads(
        (root / "benchmark/configs/kanana-2-30b-a3b-instruct-2601.json").read_text())
    env = conf["serving"]["env"]
    seq = int(env["MAX_SEQ_LEN"])
    cfg = ref.model_config(conf, seq)
    return cfg, ref.param_shapes(cfg), int(env["KV_BLOCK_TOKENS"]), int(env["MAX_BATCH_SLOTS"]), seq


def _mla_plain_table(cfg, t, seq):
    from nats_llm_studio_tpu.engine.sampling import sample_rows
    from nats_llm_studio_tpu.serve.programs import build_programs

    return build_programs(cfg, None, max_seq=seq, paged=True, kv_block_tokens=t,
                          sample_rows=sample_rows)


@pytest.mark.parametrize("program", ["decode_pallas", "decode_pallas_ext"],
                         ids=["the burst", "the single step"])
def test_a_decode_launch_of_the_plain_latent_form_at_16_slots_of_32768(
        one_chip, no_cache, mla_plain_cell, program):
    """The two decode programs as ``serve/programs.py`` builds them, all 6
    layers over the cell's pools (16 x 32,768 tokens in blocks of the
    configuration's ``KV_BLOCK_TOKENS``, 4 GB), donated: Mosaic takes the slot
    table the absorbed kernel walks, the kernel and the hit-list expert kernel
    are in the program under their names, the pools are aliased onto the
    results and nothing the size of a pool is a temporary."""
    from nats_llm_studio_tpu.ops.mla_attention import mla_paged_decode_eligible

    cfg, shapes, t, slots, seq = mla_plain_cell
    assert mla_paged_decode_eligible(t, cfg.kv_lora_rank, 2)
    nb = slots * (seq // t) + 64 + 1
    kp, vp = (jax.ShapeDtypeStruct((nb, cfg.n_layers, h, t, w), jnp.bfloat16, sharding=one_chip)
              for h, w in cfg.kv_cache_dims())
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    row = lambda dt, *more: jax.ShapeDtypeStruct(  # noqa: E731
        (slots,) + more, dt, sharding=one_chip)
    ints, floats = row(jnp.int32), row(jnp.float32)
    last = 8 if program == "decode_pallas" else row(jnp.bool_, cfg.vocab_size)
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"   # the kernels themselves, not the interpreter
    try:
        compiled = _mla_plain_table(cfg, t, seq)[program].lower(
            jax.tree.map(sds, shapes), ints, kp, vp, row(jnp.int32, seq // t), ints,
            ints, ints, floats, ints, floats, last).compile()
    finally:
        jax.default_backend = orig
    text = compiled.as_text()
    for name in ("mla_paged_decode_attention", "moe_hit_experts"):
        assert name in text, name
    assert "hc_sinkhorn" not in text   # one stream: no mixer in the program
    ma = compiled.memory_analysis()
    held = sum(int(np.prod(p.shape)) * 2 for p in (kp, vp))
    assert ma.alias_size_in_bytes >= held and ma.temp_size_in_bytes < held // 4


@pytest.mark.parametrize("width", [1, 2, 4], ids=["prefill1", "group_of_2", "group_of_4"])
def test_a_chunk_of_the_plain_latent_form_holds_no_plane_over_its_32768_window(
        one_chip, no_cache, mla_plain_cell, width):
    """A chunk of 256 tokens x ``width`` prompts through the whole cut model
    into row caches of 32,768 tokens, the window the program is built for
    (``DECODE_LADDER_RUNGS=1``): the float32 score plane [B, 32, 256, 32768]
    would be 1.07 GB a row and the window's expanded keys and values 0.54 GB;
    what the program holds beside its donated row caches stays under 1.5 GB
    at every width, because the attention walks key blocks of 256."""
    cfg, shapes, _, _, seq = mla_plain_cell
    table = _mla_plain_table(cfg, 64, seq)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    caches = [jax.ShapeDtypeStruct((width, cfg.n_layers, h, seq, w), jnp.bfloat16,
                                   sharding=one_chip) for h, w in cfg.kv_cache_dims()]
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        compiled = table["prefill1" if width == 1 else "prefill_chunk_group"].lower(
            jax.tree.map(sds, shapes), ints(width, CHUNK), *caches, ints(width), ints(width),
            seq).compile()
    finally:
        jax.default_backend = orig
    text = compiled.as_text()
    assert "moe_grouped_experts" in text and "hc_sinkhorn" not in text
    ma = compiled.memory_analysis()
    plane = width * cfg.n_heads * CHUNK * seq * 4
    assert ma.temp_size_in_bytes < min(1.5e9, plane + 0.5e9 * (width == 1)), ma.temp_size_in_bytes
    print(f"\nwidth {width}: temp {ma.temp_size_in_bytes / 1e6:.0f} MB, "
          f"alias {ma.alias_size_in_bytes / 1e6:.0f} MB")


@pytest.mark.parametrize("wide,narrow", [(4, 2), (2, 1), (4, 1)],
                         ids=["4_to_2", "2_to_1", "4_to_1"])
def test_the_row_take_of_a_narrowing_group_writes_its_narrow_pair_and_nothing_else(
        one_chip, no_cache, mla_plain_cell, wide, narrow):
    """``take_rows`` at the cell's shapes (rows of a 32,768-token latent pair,
    252 MB each): what the program holds is the narrow pair it returns, with
    no temporary the size of a row and no relayouted copy of the wide or the
    narrow cache on the way (``jnp.take`` became 16,000 lines of loops over
    128-token pieces, a concatenation of slices held every slice: either
    would double the narrowing's footprint, which HBM admission does not
    price). The one copy XLA schedules moves the 128-wide rotary plane
    between memory spaces in the layout it has."""
    cfg, _, _, _, seq = mla_plain_cell
    table = _mla_plain_table(cfg, 64, seq)
    pair = lambda b: [jax.ShapeDtypeStruct(  # noqa: E731
        (b, cfg.n_layers, h, seq, w), jnp.bfloat16, sharding=one_chip)
        for h, w in cfg.kv_cache_dims()]
    final = jax.ShapeDtypeStruct((wide, 1, cfg.vocab_size), jnp.float32, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((narrow,), jnp.int32, sharding=one_chip)
    compiled = table["take_rows"].lower(*pair(wide), final, rows).compile()
    text, ma = compiled.as_text(), compiled.memory_analysis()
    row = sum(int(np.prod(p.shape)) * 2 for p in pair(1))
    assert ma.output_size_in_bytes >= narrow * row
    assert ma.temp_size_in_bytes < row // 8, ma.temp_size_in_bytes
    for b in (wide, narrow):
        for p in pair(b):
            shape = f"bf16[{','.join(map(str, p.shape))}]"
            for found in _whole_array_copies(text, shape):
                layouts = {m.replace("S(1)", "") for m in re.findall(
                    re.escape(shape) + r"(\{[^}]*\})", found)}
                assert "copy-start(" in found and len(layouts) == 1, found
    print(f"\n{wide} -> {narrow}: out {ma.output_size_in_bytes / 1e6:.0f} MB, "
          f"temp {ma.temp_size_in_bytes / 1e6:.0f} MB")


# -- the linear-attention family at qwen3next.longanswer_closed's shapes --------


@pytest.fixture(scope="module")
def gdn_cell():
    """(cfg as the registry makes it on the chip, the served tree's shapes,
    block tokens, slots, context) of
    ``benchmark/configs/qwen3-next-80b-a3b-instruct.json``."""
    import json
    from pathlib import Path

    from benchmark import run

    root = Path(__file__).resolve().parents[1]
    ref = run.load_module(root / "benchmark/references/gdn_moe.py")
    conf = json.loads((root / "benchmark/configs/qwen3-next-80b-a3b-instruct.json").read_text())
    env = conf["serving"]["env"]
    seq = int(env["MAX_SEQ_LEN"])
    cfg = ref.model_config(conf, seq).with_(use_flash_attention=True)
    return cfg, ref.param_shapes(cfg), int(env["KV_BLOCK_TOKENS"]), int(env["MAX_BATCH_SLOTS"]), seq


def _gdn_pools(cfg, sharding, t, slots, seq):
    """The cell's pools: slots x seq / t + 1 blocks of the full layers' rows of
    head 256, each with the slots' state beside it."""
    from nats_llm_studio_tpu.models import gdn_moe
    from nats_llm_studio_tpu.ops.kvcache import WithState

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    (h, w), _ = cfg.kv_cache_dims()
    nb = slots * (seq // t) + 1
    (tail, seen), (plane,) = gdn_moe.state_shapes(cfg, slots)
    kv = lambda: sds((nb, cfg.n_kv_layers, h, t, w), jnp.bfloat16)  # noqa: E731
    return (WithState(kv(), (sds(tail, jnp.bfloat16), sds(seen, jnp.int32)), gdn_moe.K_AXES),
            WithState(kv(), (sds(plane, jnp.float32),), gdn_moe.V_AXES))


def _gdn_step_shapes(cfg, one_chip, slots):
    """(heads, consts, qkvz, ba) of one linear layer's decode step at the cell's shapes."""
    from nats_llm_studio_tpu.ops import gated_delta

    sds = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    f32, bf16 = jnp.float32, jnp.bfloat16
    hk, dk, h, dv = cfg.lin_k_heads, cfg.lin_k_dim, cfg.lin_v_heads, cfg.lin_v_dim
    ll, c = cfg.n_lin_layers, cfg.lin_conv_dim
    consts = gated_delta.StepConsts(
        sds(f32, ll, cfg.ssm_conv, c), sds(f32, ll, 1, h), sds(f32, ll, 1, h),
        sds(f32, ll, 1, dv), sds(f32, 1), sds(jnp.int32, slots, 1))
    return (hk, dk, h, dv), consts, sds(bf16, slots, c + h * dv), sds(bf16, slots, 2 * h)


def test_gated_delta_step_at_the_benchmark_cells_shapes(one_chip, no_cache, gdn_cell):
    """One layer's step over the state pool [32, 9, 32, 128, 128] f32 (0.6 GB
    at 12 layers), the live slots a traced mask, the operands as
    ``step_inputs`` leaves them (16 key heads for 32 value heads, decay and
    beta as scalars, z out of the in-projection's own output): Mosaic tiles it
    (the key heads' transposition to columns, the read-out rows kept by slot,
    the gated norm in the last cell), the list rides in as scalars, and the
    pool is the result's own buffer."""
    from nats_llm_studio_tpu.ops import gated_delta, ssm_scan

    cfg, _, t, slots, seq = gdn_cell
    _, vp = _gdn_pools(cfg, one_chip, t, slots, seq)
    pool = vp.st[0]
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
    (hk, dk, h, dv), consts, qkvz, _ = _gdn_step_shapes(cfg, one_chip, slots)
    compiled = jax.jit(
        lambda pool, layer, mask, decay, beta, q, k, v, zs, gain, eps: gated_delta.gated_delta_step(
            pool, layer, ssm_scan.live_slots(mask), decay, beta, q, k,
            gated_delta.Values(v, zs, gain, eps)),
        donate_argnums=(0,)).lower(
        pool, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
        f32(slots, h), f32(slots, h), f32(slots, hk, dk), f32(slots, hk, dk),
        f32(slots, h, dv), qkvz, consts.gain, consts.eps).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gated_delta_step" in text
    ma = compiled.memory_analysis()
    pool_bytes = int(np.prod(pool.shape)) * 4
    assert ma.alias_size_in_bytes >= pool_bytes and ma.temp_size_in_bytes < pool_bytes // 8


def test_the_linear_layers_step_inputs_at_the_benchmark_cells_shapes(one_chip, no_cache, gdn_cell):
    """The call before the state kernel at the cell's shapes (4 taps x 32 slots
    x 8,192 channels, bf16 in, float32 out): Mosaic takes a head's 128 channels
    at a traced offset out of the tap planes and puts its rows into the [16,
    heads, 128] blocks, and the tail is the result's own buffer."""
    from nats_llm_studio_tpu.ops import gated_delta

    cfg, _, _, slots, _ = gdn_cell
    heads, consts, qkvz, ba = _gdn_step_shapes(cfg, one_chip, slots)
    tail = jax.ShapeDtypeStruct((cfg.ssm_conv, slots, cfg.lin_conv_dim), jnp.bfloat16,
                                sharding=one_chip)
    compiled = jax.jit(
        lambda qkvz, ba, tail, layer, consts: gated_delta.step_inputs(
            qkvz, ba, tail, layer, consts, heads),
        donate_argnums=(2,)).lower(
        qkvz, ba, tail, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip), consts).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gdn_step_inputs" in text
    assert compiled.memory_analysis().alias_size_in_bytes >= int(np.prod(tail.shape)) * 2


def _gdn_table(cfg, t, seq):
    from nats_llm_studio_tpu.engine.sampling import sample_rows
    from nats_llm_studio_tpu.serve.programs import build_programs

    return build_programs(cfg, None, max_seq=seq, paged=True, kv_block_tokens=t,
                          sample_rows=sample_rows)


@pytest.mark.parametrize("program", ["decode_pallas", "decode_pallas_ext"],
                         ids=["the burst", "the single step"])
def test_a_decode_launch_of_the_linear_attention_family_copies_no_pool(
        one_chip, no_cache, gdn_cell, program):
    """The family's two decode programs as ``serve/programs.py`` builds them,
    all the cut's layers over the cell's pools (32 slots x 8,192 tokens),
    donated: the state kernel, the paged attention kernel at head 256 and the
    hit-list expert kernel at 128 held of 512 are in the program under their
    names, the pools are aliased onto the results, and no ``copy`` of the
    float32 state pool, of the convolution tails or of a KV pool is anywhere
    in it. A linear layer's body holds at most 10 device operations under
    ``seq/linear``, two of them Pallas calls, and one call alone carries the
    state kernel's name (the benchmark finds it by that substring)."""
    cfg, shapes, t, slots, seq = gdn_cell
    assert paged_decode_eligible(t, cfg.head_dim, 2, False, cfg.n_kv_heads, 1)
    kp, vp = _gdn_pools(cfg, one_chip, t, slots, seq)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    row = lambda dt, *more: jax.ShapeDtypeStruct(  # noqa: E731
        (slots,) + more, dt, sharding=one_chip)
    ints, floats = row(jnp.int32), row(jnp.float32)
    last = 8 if program == "decode_pallas" else row(jnp.bool_, cfg.vocab_size)
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"   # the kernels themselves, not the interpreter
    try:
        compiled = _gdn_table(cfg, t, seq)[program].lower(
            jax.tree.map(sds, shapes), ints, kp, vp, row(jnp.int32, seq // t), ints, ints, ints,
            floats, ints, floats, last).compile()
    finally:
        jax.default_backend = orig
    text = compiled.as_text()
    for name in ("gated_delta_step", "gdn_step_inputs", "paged_decode_attention",
                 "moe_hit_experts"):
        assert name in text, name
    # a linear layer's body: three products, the mix norm's two, the tail's
    # slice and its write-back, and the two Pallas calls (40 before the step's
    # small operations moved into the calls)
    bodies = [ops for ops in _scoped_operations(text, "seq/linear").values()
              if any("gated_delta_step" in ln for _, ln in ops)]
    assert len(bodies) == 1, [len(b) for b in bodies]
    (ops,) = bodies
    assert len(ops) <= 10, [ln[:100] for _, ln in ops]
    calls = [ln.split(" = ", 1)[0] for code, ln in ops if code == "custom-call"]
    assert sum("gated_delta_step" in c for c in calls) == 1 and len(calls) == 2, calls
    state, kv, tails = vp.st[0], kp.kv, kp.st[0]
    pools = (f"f32[{','.join(map(str, state.shape))}]", f"bf16[{','.join(map(str, kv.shape))}]",
             f"bf16[{','.join(map(str, tails.shape))}]")
    copies = [ln.strip()[:160] for ln in text.splitlines()
              if (" copy(" in ln or "copy-start(" in ln) and any(p in ln for p in pools)]
    assert not copies, copies
    ma = compiled.memory_analysis()
    state_bytes = int(np.prod(state.shape)) * 4
    assert ma.alias_size_in_bytes >= state_bytes + 2 * int(np.prod(kv.shape)) * 2
    assert ma.temp_size_in_bytes < state_bytes // 2, ma.temp_size_in_bytes
    print(f"\n{program}: temp {ma.temp_size_in_bytes / 1e6:.0f} MB, "
          f"alias {ma.alias_size_in_bytes / 1e6:.0f} MB, args {ma.argument_size_in_bytes / 1e9:.2f} GB")


@pytest.mark.parametrize("width", [1, 4], ids=["prefill1", "group_of_4"])
def test_a_chunk_of_the_linear_attention_family_at_the_cells_shapes(
        one_chip, no_cache, gdn_cell, width):
    """A chunk of 256 tokens x ``width`` prompts through the whole cut model
    into row caches of 8,192 tokens with their state beside them: the chunked
    rule (its triangular solve lowers for the chip), the flash chunk kernel at
    head 256 and the grouped expert kernel at 128 held of 512, and what the
    program holds beside its donated row caches stays under 1.5 GB."""
    cfg, shapes, _, _, seq = gdn_cell
    table = _gdn_table(cfg, 16, seq)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    from nats_llm_studio_tpu.models import gdn_moe

    caches = jax.tree.map(sds, jax.eval_shape(lambda: gdn_moe.make_cache(cfg, width, seq)))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        compiled = table["prefill1" if width == 1 else "prefill_chunk_group"].lower(
            jax.tree.map(sds, shapes), ints(width, CHUNK), *caches, ints(width), ints(width),
            seq).compile()
    finally:
        jax.default_backend = orig
    text = compiled.as_text()
    assert "moe_grouped_experts" in text and "flash" in text
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 1.5e9, ma.temp_size_in_bytes
    print(f"\nwidth {width}: temp {ma.temp_size_in_bytes / 1e6:.0f} MB, "
          f"alias {ma.alias_size_in_bytes / 1e6:.0f} MB")


# -- the lightning / block-sparse family at minicpmsala.longdoc_closed's shapes ---


@pytest.fixture(scope="module")
def sala_cell():
    """(cfg as the registry makes it on the chip, the served tree's shapes,
    block tokens, slots, context) of ``benchmark/configs/minicpm-sala.json``."""
    import json
    from pathlib import Path

    from benchmark import run

    root = Path(__file__).resolve().parents[1]
    ref = run.load_module(root / "benchmark/references/sala.py")
    conf = json.loads((root / "benchmark/configs/minicpm-sala.json").read_text())
    env = conf["serving"]["env"]
    seq = int(env["MAX_SEQ_LEN"])
    cfg = ref.model_config(conf, seq).with_(use_flash_attention=True)
    return cfg, ref.param_shapes(cfg), int(env["KV_BLOCK_TOKENS"]), int(env["MAX_BATCH_SLOTS"]), seq


def _sala_pools(cfg, sharding, t, slots, seq):
    """The cell's pools: slots x seq / t + 1 blocks of the sparse layers' rows,
    the slots' pooled keys beside K's and their state beside V's."""
    from nats_llm_studio_tpu.models import sala
    from nats_llm_studio_tpu.ops.kvcache import WithState

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    nb = slots * (seq // t) + 1
    (pooled, seen), (plane,) = sala.state_shapes(cfg, slots)
    kv = lambda: sds((nb, cfg.n_kv_layers, cfg.n_kv_heads, t, cfg.head_dim), jnp.bfloat16)  # noqa: E731
    return (WithState(kv(), (sds(pooled, jnp.bfloat16), sds(seen, jnp.int32)), sala.K_AXES),
            WithState(kv(), (sds(plane, jnp.float32),), sala.V_AXES))


def test_lightning_step_at_the_benchmark_cells_shapes(one_chip, no_cache, sala_cell):
    """One layer's step over the state pool [16, 6, 32, 128, 128] f32 (0.2 GB),
    the live slots a traced list: Mosaic takes the blocks of heads, and the
    pool is aliased onto the result."""
    from nats_llm_studio_tpu.ops import lightning, ssm_scan

    cfg, _, t, slots, seq = sala_cell
    _, vp = _sala_pools(cfg, one_chip, t, slots, seq)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
    h, d = cfg.lin_v_heads, cfg.lin_k_dim
    live = ssm_scan.LiveSlots(jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
                              jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
                              jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    compiled = _compile(
        lambda pool, layer, live, decay, q, k, v: lightning.lightning_step(
            pool, layer, live, decay, q, k, v),
        vp.st[0], jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip), live,
        f32(slots, h), f32(slots, h, d), f32(slots, h, d), f32(slots, h, cfg.lin_v_dim))
    assert "lightning_step" in compiled.as_text()


def test_the_picked_walk_at_the_benchmark_cells_shapes(one_chip, no_cache, sala_cell):
    """The picked walk at 16 slots x 2 kv heads, a table of 128 entries a (slot,
    kv head) of blocks of 64 tokens: one head's [64, 128] slab a copy, runs of 8."""
    from nats_llm_studio_tpu.ops.paged_attention import paged_decode_attention_picked

    cfg, _, t, slots, seq = sala_cell
    kp, vp = _sala_pools(cfg, one_chip, t, slots, seq)
    sds = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    width = max(cfg.sparse_topk, cfg.sparse_dense_len // cfg.sparse_block)
    compiled = _compile(
        lambda q, k, v, e, c, ll, layer: paged_decode_attention_picked(
            q, k, v, e, c, ll, layer, cfg.attn_scale),
        sds(jnp.bfloat16, slots, 1, cfg.n_heads, cfg.head_dim), kp.kv, vp.kv,
        sds(jnp.int32, slots, cfg.n_kv_heads, width), sds(jnp.int32, slots, cfg.n_kv_heads),
        sds(jnp.int32, slots), sds(jnp.int32))
    assert "paged_decode_attention_picked" in compiled.as_text()


@pytest.mark.parametrize("program", ["decode_pallas", "decode_pallas_ext"],
                         ids=["the burst", "the single step"])
def test_a_decode_launch_of_the_lightning_family_copies_no_pool(
        one_chip, no_cache, sala_cell, program):
    """The family's two decode programs as ``serve/programs.py`` builds them,
    the cut's 8 layers over the cell's pools (16 slots x 28,672 tokens),
    donated: the state kernel and the picked walk are in the program under
    their names, the pools are aliased onto the results, and no ``copy`` of the
    float32 state pool, of the pooled keys or of a KV pool is anywhere in it,
    and what it holds beside its pools is under 100 MB (8 MB as compiled; the
    parent of PR 53 held the Lightning stack's wq, wk and wv transposed, 0.6 GB,
    once a launch)."""
    cfg, shapes, t, slots, seq = sala_cell
    kp, vp = _sala_pools(cfg, one_chip, t, slots, seq)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    row = lambda dt, *more: jax.ShapeDtypeStruct(  # noqa: E731
        (slots,) + more, dt, sharding=one_chip)
    ints, floats = row(jnp.int32), row(jnp.float32)
    last = 8 if program == "decode_pallas" else row(jnp.bool_, cfg.vocab_size)
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"   # the kernels themselves, not the interpreter
    try:
        compiled = _gdn_table(cfg, t, seq)[program].lower(
            jax.tree.map(sds, shapes), ints, kp, vp, row(jnp.int32, seq // t), ints, ints, ints,
            floats, ints, floats, last).compile()
    finally:
        jax.default_backend = orig
    text = compiled.as_text()
    for name in ("lightning_step", "paged_decode_attention_picked"):
        assert name in text, name
    state, kv, pooled = vp.st[0], kp.kv, kp.st[0]
    pools = (f"f32[{','.join(map(str, state.shape))}]", f"bf16[{','.join(map(str, kv.shape))}]",
             f"bf16[{','.join(map(str, pooled.shape))}]")
    copies = [ln.strip()[:160] for ln in text.splitlines()
              if (" copy(" in ln or "copy-start(" in ln) and any(p in ln for p in pools)]
    assert not copies, copies
    ma = compiled.memory_analysis()
    state_bytes = int(np.prod(state.shape)) * 4
    assert ma.alias_size_in_bytes >= state_bytes + 2 * int(np.prod(kv.shape)) * 2
    assert ma.temp_size_in_bytes < 100e6, ma.temp_size_in_bytes
    print(f"\n{program}: temp {ma.temp_size_in_bytes / 1e6:.0f} MB, "
          f"alias {ma.alias_size_in_bytes / 1e6:.0f} MB, args {ma.argument_size_in_bytes / 1e9:.2f} GB")


@pytest.mark.parametrize("width", [1, 4], ids=["prefill1", "group_of_4"])
def test_a_chunk_of_the_lightning_family_at_the_cells_shapes(
        one_chip, no_cache, sala_cell, width):
    """A chunk of 256 tokens x ``width`` prompts through the cut model into row
    caches of 28,672 tokens with their state and pooled keys beside them: the
    chunked rule, the flash chunk kernel under the dense length and the masked
    attention past it in one program, and what the program holds beside its
    donated row caches stays under 2.5 GB."""
    cfg, shapes, t, _, seq = sala_cell
    table = _gdn_table(cfg, t, seq)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    from nats_llm_studio_tpu.models import sala

    caches = jax.tree.map(sds, jax.eval_shape(lambda: sala.make_cache(cfg, width, seq)))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        compiled = table["prefill1" if width == 1 else "prefill_chunk_group"].lower(
            jax.tree.map(sds, shapes), ints(width, CHUNK), *caches, ints(width), ints(width),
            seq).compile()
    finally:
        jax.default_backend = orig
    text = compiled.as_text()
    assert "flash" in text and "conditional" in text
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 2.5e9, ma.temp_size_in_bytes
    print(f"\nwidth {width}: temp {ma.temp_size_in_bytes / 1e6:.0f} MB, "
          f"alias {ma.alias_size_in_bytes / 1e6:.0f} MB")


# -- the state-space family with layers of latent experts at nemotron3super.agent64_closed's shapes --


LMOE_SLOTS = 64


@pytest.fixture(scope="module")
def lmoe_cell():
    """(cfg, the served tree's shapes) of ``benchmark/configs/nemotron-3-super-120b-a12b.json``."""
    import json
    from pathlib import Path

    from benchmark import run

    root = Path(__file__).resolve().parents[1]
    ref = run.load_module(root / "benchmark/references/ssm_latent_moe.py")
    conf = json.loads((root / "benchmark/configs/nemotron-3-super-120b-a12b.json").read_text())
    cfg = ref.model_config(conf, SEQ)
    return cfg, ref.param_shapes(cfg)


def _lmoe_pools(cfg, sharding):
    """The cell's pools: 64 x 256 + 1 blocks of the one attention layer, each
    with the 64 slots' state of the five Mamba-2 layers beside it."""
    from nats_llm_studio_tpu.models import ssm_hybrid
    from nats_llm_studio_tpu.ops.kvcache import WithState

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    (h, w), _ = cfg.kv_cache_dims()
    nb = LMOE_SLOTS * (SEQ // T) + 1
    (tail, seen), (plane,) = ssm_hybrid.state_shapes(cfg, LMOE_SLOTS)
    kv = lambda: sds((nb, cfg.n_kv_layers, h, T, w), jnp.bfloat16)  # noqa: E731
    return (WithState(kv(), (sds(tail, jnp.bfloat16), sds(seen, jnp.int32)), ssm_hybrid.K_AXES),
            WithState(kv(), (sds(plane, jnp.float32),), ssm_hybrid.V_AXES))


def test_a_decode_burst_of_the_latent_expert_family_copies_no_pool_and_no_stack(
        one_chip, no_cache, lmoe_cell):
    """The burst decode program as ``serve/programs.py`` builds it for a
    family with expert layers (eight steps, their sampling and the expert
    counters), all 11 layers over the cell's pools, donated: the (mamba,
    experts) pair scanned three times and the five layers after it on their
    own, the state kernel at 8 groups, the paged attention kernel and a
    two-matrix expert kernel in it under their names, the pools aliased onto
    the results, no ``copy`` of the float32 state pool, of the convolution
    tails or of the KV pool, and no layer's slice of the expert stacks (1.4 GB)
    among the temporaries. It fits the chip beside 9.3 GB of weights."""
    from nats_llm_studio_tpu.engine.sampling import sample_rows
    from nats_llm_studio_tpu.serve.programs import build_programs

    cfg, shapes = lmoe_cell
    assert (cfg.n_ssm_layers, cfg.n_moe_layers, cfg.n_kv_layers) == (5, 5, 1)
    kp, vp = _lmoe_pools(cfg, one_chip)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    row = lambda dt, *more: jax.ShapeDtypeStruct(  # noqa: E731
        (LMOE_SLOTS,) + more, dt, sharding=one_chip)
    ints, floats = row(jnp.int32), row(jnp.float32)
    table = build_programs(cfg, None, max_seq=SEQ, paged=True, kv_block_tokens=T,
                           sample_rows=sample_rows)
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"   # the kernels themselves, not the interpreter
    try:
        compiled = table["decode_pallas"].lower(
            jax.tree.map(sds, shapes), ints, kp, vp, row(jnp.int32, SEQ // T), ints, ints, ints,
            floats, ints, floats, 8).compile()
    finally:
        jax.default_backend = orig
    text = compiled.as_text()
    assert "ssm_state_step" in text and "paged_decode_attention" in text
    assert "moe_hit_experts" in text or "moe_grouped_experts" in text
    state, kv, tails = vp.st[0], kp.kv, kp.st[0]
    assert state.shape == (LMOE_SLOTS, 5, 64, 128, 128)
    pools = (f"f32[{','.join(map(str, state.shape))}]", f"bf16[{','.join(map(str, kv.shape))}]",
             f"bf16[{','.join(map(str, tails.shape))}]")
    # a copy-start whose target lies in another memory space (S(1)) is the
    # compiler's prefetch of the 26 MB of tails, not a second pool in HBM
    copies = [ln.strip()[:160] for ln in text.splitlines()
              if (" copy(" in ln or "copy-start(" in ln) and any(p in ln for p in pools)
              and "S(1)" not in ln]
    assert not copies, copies
    assert tails.shape == (5, cfg.ssm_conv, LMOE_SLOTS, cfg.ssm_conv_dim)
    assert _tail_tiles(text, tails) == {"T(8,128)(2,1)"}   # a tap a plane (PR 57)
    assert not _expert_stack_copies(text, cfg)
    ma = compiled.memory_analysis()
    state_bytes = int(np.prod(state.shape)) * 4
    assert ma.alias_size_in_bytes >= state_bytes + 2 * int(np.prod(kv.shape)) * 2
    assert ma.temp_size_in_bytes < state_bytes // 8
    assert (ma.argument_size_in_bytes + ma.temp_size_in_bytes) < 15 * 2**30


def _expert_stack_copies(text: str, cfg) -> list[str]:
    """Lines that make a layer's slice (or all) of an expert stack anew."""
    e, w, f = cfg.n_experts_held, cfg.moe_latent, cfg.moe_d_ff
    shapes = [f"{e},{w},{f}]", f"{e},{f},{w}]"]
    made = (" copy(", "copy-start(", " dynamic-slice(")
    return [ln.strip()[:160] for ln in text.splitlines()
            if any(m in ln for m in made)
            and any(x in ln.partition(" = ")[2][:80] for x in shapes)]


@pytest.mark.parametrize("width", [1, 4], ids=["prefill1", "chunk_group_of_4"])
def test_a_prefill_chunk_of_the_latent_expert_family_fits_and_copies_no_stack(
        one_chip, no_cache, lmoe_cell, width):
    """A chunk of 256 tokens x ``width`` prompts through the cut model: the
    chunked scan at 8 groups of 16 heads in chunks of 128, the grouped
    two-matrix kernel between the latent pair, the expert stacks read where
    they lie."""
    from nats_llm_studio_tpu.models import llama, ssm_hybrid
    from nats_llm_studio_tpu.ops.kvcache import WithState

    cfg, shapes = lmoe_cell
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    (h, w), _ = cfg.kv_cache_dims()
    (tail, seen), (plane,) = ssm_hybrid.state_shapes(cfg, width)
    kv = lambda: s((width, cfg.n_kv_layers, h, SEQ, w), jnp.bfloat16)  # noqa: E731
    caches = (WithState(kv(), (s(tail, jnp.bfloat16), s(seen, jnp.int32)), ssm_hybrid.K_AXES),
              WithState(kv(), (s(plane, jnp.float32),), ssm_hybrid.V_AXES))
    ints = lambda *shape: s(shape, jnp.int32)  # noqa: E731
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        compiled = _compile(
            lambda params, tokens, k, v, start, last: llama.forward(
                params, cfg, tokens, k, v, start, logit_positions=last, uniform_start=True),
            jax.tree.map(sds, shapes), ints(width, CHUNK), *caches, ints(width), ints(width))
    finally:
        jax.default_backend = orig
    text = compiled.as_text()
    assert text.count("moe_grouped_experts") >= 1 and "moe_hit_experts" not in text
    assert not _expert_stack_copies(text, cfg)
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 2 * 2**30   # 1.3 GB at width 4: the chunk's activations
    assert (ma.argument_size_in_bytes + ma.temp_size_in_bytes) < 15 * 2**30
