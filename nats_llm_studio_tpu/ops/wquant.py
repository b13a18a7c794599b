"""Weight-only int8 and grouped int4 quantization for serving.

Decode throughput is bound by streaming the weights from HBM once per step
(SURVEY.md §7 hard part #5); storing matmul weights as int8 with a
per-output-channel scale halves that traffic vs bf16 and is what makes
Llama-3-70B fit on a v5e-8 (BASELINE.md config 3: 8 x 16 GB HBM cannot hold
140 GB of bf16). The reference gets the same capability from llama.cpp's
quantized GGUF kernels inside LM Studio (/root/reference/README.md:3-7);
here it is a first-class device representation, not a file format.

``QTensor`` is a pytree (int8 codes + broadcastable scale), so quantized
params flow through jit / lax.scan / shard_map unchanged — scan slices the
leading [L] axis off both leaves. ``mm``/``q_einsum`` dequantize on the fly:
XLA fuses convert(s8->bf16)*scale into the matmul's operand read, so HBM
moves int8 bytes.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclass
class QTensor:
    """Symmetric per-output-channel int8 weight: ``w ≈ q * s``.

    q: int8, the original weight shape [..., in, out]
    s: f32, [..., 1, out] — broadcastable over the contraction axis
    """

    q: jax.Array
    s: jax.Array

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    def dequant(self, dtype=jnp.bfloat16) -> jax.Array:
        return (self.q.astype(jnp.float32) * self.s).astype(dtype)


@jax.tree_util.register_dataclass
@dataclass
class QTensor4:
    """Asymmetric grouped int4 weight: ``w ≈ (q - z) * s`` per group.

    AWQ/GPTQ-style storage: the contraction axis is cut into groups of
    ``group`` rows, each with its own scale and zero point, and two 4-bit
    codes pack into one byte (even row in the low nibble, odd in the high).

    q: uint8, [..., in/2, out] — packed nibble pairs along the contraction axis
    s: f32,   [..., in/group, out] — per-group scale
    z: f32,   [..., in/group, out] — per-group zero point, in code units
    group: static metadata (rows per group), not a pytree leaf
    """

    q: jax.Array
    s: jax.Array
    z: jax.Array
    group: int = field(metadata=dict(static=True), default=32)

    @property
    def shape(self):
        # logical (unpacked) weight shape
        return (*self.q.shape[:-2], self.q.shape[-2] * 2, self.q.shape[-1])

    @property
    def ndim(self):
        return self.q.ndim

    def codes(self) -> jax.Array:
        """Unpack nibbles back to int32 codes in [0, 15], shape [..., in, out]."""
        lo = (self.q & 0x0F).astype(jnp.int32)
        hi = (self.q >> 4).astype(jnp.int32)
        # rows 2i came from the low nibble, 2i+1 from the high nibble
        both = jnp.stack([lo, hi], axis=-2)  # [..., in/2, 2, out]
        return both.reshape(self.shape)

    def dequant(self, dtype=jnp.bfloat16) -> jax.Array:
        c = self.codes().astype(jnp.float32)
        s = jnp.repeat(self.s, self.group, axis=-2)
        z = jnp.repeat(self.z, self.group, axis=-2)
        return ((c - z) * s).astype(dtype)


def effective_group(in_dim: int, group: int) -> int:
    """Largest even group <= ``group`` that divides ``in_dim``.

    Tiny test models (d_model 64) cannot honor the production default of
    128, so the group degrades instead of erroring; 2 always divides an
    even contraction axis (packing already requires in_dim % 2 == 0).
    """
    g = max(2, min(group, in_dim))
    while in_dim % g or g % 2:
        g -= 1
        if g < 2:
            raise ValueError(f"no valid int4 group for in_dim={in_dim}")
    return g


def quantize_weight4(w: np.ndarray | jax.Array, group: int = 32,
                     device: bool = False) -> QTensor4:
    """Asymmetric min/max int4 over groups of the contraction axis.

    Host-side NumPy by default (streaming loaders quantize one tensor at a
    time); ``device=True`` runs the same math in jnp.
    """
    xp = jnp if device else np
    w = w.astype(xp.float32) if device else np.asarray(w, dtype=np.float32)
    in_dim = w.shape[-2]
    if in_dim % 2:
        raise ValueError(f"int4 packing needs an even contraction axis, got {in_dim}")
    g = effective_group(in_dim, group)
    ng = in_dim // g
    wg = w.reshape(*w.shape[:-2], ng, g, w.shape[-1])
    wmin = xp.min(wg, axis=-2)
    wmax = xp.max(wg, axis=-2)
    s = (wmax - wmin) / 15.0
    safe = xp.where(s == 0, 1.0, s)
    z = xp.clip(xp.round(-wmin / safe), 0.0, 15.0)
    q = xp.clip(xp.round(wg / safe[..., None, :]) + z[..., None, :], 0.0, 15.0)
    q = q.reshape(w.shape).astype(xp.uint8)
    lo = q[..., 0::2, :]
    hi = q[..., 1::2, :]
    packed = (lo | (hi << 4)).astype(xp.uint8)
    return QTensor4(q=packed, s=safe.astype(xp.float32),
                    z=z.astype(xp.float32), group=g)


def _mm4(x: jax.Array, w: QTensor4) -> jax.Array:
    """Fused grouped dequant-matmul: HBM streams packed int4 bytes.

    Expands ``x @ ((q - z) * s)`` into per-group partial dots so the codes
    feed the matmul directly (no [in, out] float weight is materialized):
    ``sum_g s_g * (x_g @ q_g) - sum_g (s_g * z_g) * sum(x_g)``.
    """
    in_dim, out = w.shape[-2], w.shape[-1]
    ng = in_dim // w.group
    xr = x.reshape(*x.shape[:-1], ng, w.group)
    cg = w.codes().astype(x.dtype).reshape(ng, w.group, out)
    t = jnp.einsum("...ng,ngo->...no", xr, cg)
    y = jnp.sum(t * w.s.astype(x.dtype), axis=-2)
    corr = jnp.einsum("...n,no->...o", xr.sum(axis=-1),
                      (w.s * w.z).astype(x.dtype))
    return y - corr


def quantize_weight(w: np.ndarray | jax.Array, device: bool = False) -> QTensor:
    """Symmetric absmax int8 over the contraction (second-to-last) axis.

    Host-side NumPy by default so the streaming 70B loader can quantize one
    tensor at a time without touching the device; ``device=True`` runs the
    same math in jnp for already-placed arrays.
    """
    xp = jnp if device else np
    w = w if device else np.asarray(w, dtype=np.float32)
    amax = xp.max(xp.abs(w.astype(xp.float32) if device else w), axis=-2, keepdims=True)
    s = amax / 127.0
    safe = xp.where(s == 0, 1.0, s)
    q = xp.clip(xp.round(w / safe), -127, 127).astype(xp.int8)
    return QTensor(q=q, s=safe.astype(xp.float32))


def mm(x: jax.Array, w) -> jax.Array:
    """``x @ w`` for plain arrays, QTensor, or QTensor4 (dequant-in-matmul)."""
    if isinstance(w, QTensor):
        y = jnp.matmul(x, w.q.astype(x.dtype))
        return y * w.s.astype(x.dtype)
    if isinstance(w, QTensor4):
        if w.q.ndim == 2:
            return _mm4(x, w)
        # leading batch axes (unsliced stacks): plain dequant matmul — XLA
        # still fuses the unpack into the operand read
        return jnp.matmul(x, w.dequant(x.dtype))
    return x @ w


def flat_rows(*rows: jax.Array):
    """Products of ``mm`` held as the flat rows [B, T, H * D] they are, apart
    from the cut into heads that follows them: ``q, k, v = flat_rows(mm(h,
    p["wq"]), ...)`` and only then ``q.reshape(b, t, heads, d)``. One array
    in, that array out; several, their tuple.

    Left to itself XLA:TPU folds the reshape into the product
    (``%bitcast_multiply_fusion = bf16[8,1,32,128]{3,0,2,1}`` in a compiled
    burst): the weight operand becomes a bitcast of the layer's slice to
    [d_model, heads, head_dim], which cannot be fused as the slice of the stack
    that the MLP's and ``wo``'s products read in place, and that form wants
    the contraction axis minor. A decode burst then begins by copying the
    whole ``wq`` / ``wk`` / ``wv`` stacks to ``{1,2,0}`` and every layer of
    every step copies its slice of those copies
    (``%constant_dynamic-slice_fusion``), and the one-row prefill and admit
    programs hold a slice and a relayout a stack a layer: 2.04 ms of Granite-8B's
    14.3 ms step (PERF.md section 6, PR 52), three copies of 33.5 MB a Lightning
    layer and 0.6 GB of temporaries a launch in MiniCPM-SALA (PR 53);
    tests/test_tpu_compile.py reads the compiled text of every family. The
    barrier costs nothing and changes no value: behind it the products are
    plain [rows, d_model] x [d_model, cols], each streaming its slice from
    the stack at rest, bf16 and int8 alike (packed int4 never took that
    form). It cures a reshape of a product's OUTPUT only: a weight that is
    itself cut into heads (``mla_moe._w_ukv``) is still copied a layer."""
    held = jax.lax.optimization_barrier(rows)
    return held[0] if len(rows) == 1 else held


def q_einsum(spec: str, x: jax.Array, w) -> jax.Array:
    """``einsum(spec, x, w)`` with QTensor/QTensor4 support.

    Requires the weight's contraction axis to be its second-to-last (where
    the scale has extent 1). The scale is permuted/broadcast to the output
    label order, so any output layout works ("btd,edf->btef",
    "ecd,edf->ecf", ...).
    """
    if isinstance(w, QTensor4):
        # grouped scales don't broadcast over arbitrary einsum layouts; the
        # unpack+dequant chain is elementwise so it fuses into the einsum
        return jnp.einsum(spec, x, w.dequant(x.dtype))
    if not isinstance(w, QTensor):
        return jnp.einsum(spec, x, w)
    y = jnp.einsum(spec, x, w.q.astype(x.dtype))
    ins, out = spec.split("->")
    wsub = ins.split(",")[1]
    kept = [l for l in out if l in wsub]
    # the reduced labels all have extent 1 in the scale, so this einsum is a
    # squeeze+permute into output label order
    s = jnp.einsum(f"{wsub}->{''.join(kept)}", w.s)
    shape = [s.shape[kept.index(l)] if l in kept else 1 for l in out]
    return y * s.reshape(shape).astype(x.dtype)


_QUANT_KEYS = frozenset(
    {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
     "w_gate_e", "w_up_e", "w_down_e", "lm_head"}
)


def quantizable(key: str) -> bool:
    """Whether a params-pytree leaf (by last path segment) should be int8.

    Norms and the router stay high precision (tiny, accuracy-critical); the
    embedding stays bf16 because it is read by gather, not matmul.
    """
    return key.rsplit(".", 1)[-1] in _QUANT_KEYS


def quantize_params(params: dict, mode: str = "int8", group: int = 32) -> dict:
    """Quantize, on the host, every eligible leaf of a materialized params
    pytree — for trees that exist already (``init_params`` in tests). Serving
    never materializes one: the loader (parallel/loader.py) quantizes tensor
    by tensor with the same ``quantize_weight`` / ``quantize_weight4``.

    ``mode``: "int8" (per-output-channel QTensor) or "int4" (grouped
    QTensor4, ``group`` rows per scale/zero-point).
    """
    if mode not in ("int8", "int4"):
        raise ValueError(f"unknown weight quant mode: {mode!r}")

    def quant_one(v):
        if mode == "int4":
            return quantize_weight4(np.asarray(v), group=group)
        return quantize_weight(np.asarray(v))

    def walk(node: dict, prefix: str = "") -> dict:
        out = {}
        for k, v in node.items():
            path = f"{prefix}{k}"
            if isinstance(v, dict):
                out[k] = walk(v, f"{path}.")
            elif quantizable(path) and not isinstance(v, (QTensor, QTensor4)):
                out[k] = quant_one(v)
            else:
                out[k] = v
        return out

    return walk(params)
