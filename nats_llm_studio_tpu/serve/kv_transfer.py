"""Block-transfer wire format for disaggregated prefill/decode serving.

A prefill-role worker gathers a request's finished KV blocks to host memory
(per prefill chunk: one [1, L, Hkv, C, D] K row and V row — bf16/f32 dense,
or int8 KVQ codes plus [1, L, Hkv, C] f32 scales — with the chunk-end logits
where the prefill harvested them) and ships the set to a decode-role peer.
This module owns ONLY the byte layout of that shipment; the transport
(chunked NATS publishes or the JetStream Object Store) treats the blob as
opaque bytes under a SHA-256 digest.

Layout (all integers little-endian):

    magic   b"KVX1"
    u32     header length
    header  canonical JSON (sorted keys) describing layout/dtypes/shapes,
            the covered token ids, and which chunks carry logits
    body    per chunk, in order: K codes, [K scales], V codes, [V scales],
            [logits f32] — raw C-order array bytes, sizes derivable from
            the header alone

The format is pinned by golden fixtures in tests/test_wire_goldens.py: a
silent serialization change corrupts shipped KV on mixed-version clusters,
so any byte-level change must bump the magic and regenerate the goldens.
"""

from __future__ import annotations

import json
import struct

import numpy as np

MAGIC = b"KVX1"

_MAX_HEADER_BYTES = 16 << 20  # corrupt-length guard, far above any real header


class KVTransferFormatError(ValueError):
    """The blob is not a well-formed KV transfer payload."""


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        # bfloat16 lives in ml_dtypes (a jax dependency) until the import
        # registers it with numpy
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _leaf_pair(arr):
    """Normalize a chunk leaf: dense ndarray -> (codes, None); a KVQ-style
    (codes, scales) pair passes through."""
    if isinstance(arr, tuple):
        q, s = arr
        return np.ascontiguousarray(q), np.ascontiguousarray(s)
    return np.ascontiguousarray(arr), None


def encode_kv_blob(export: dict) -> bytes:
    """Serialize one prefill export.

    ``export`` is the dict ``ContinuousBatcher.export_prefix_blocks``
    returns: ``token_ids`` (covered prompt ids), ``chunk_tokens`` (C), and
    ``chunks`` — per prefill chunk a dict with ``k``/``v`` leaves (ndarray,
    or ``(codes, scales)`` for KVQ) and optional ``logits`` (f32 [vocab]).
    """
    chunks = export["chunks"]
    if not chunks:
        raise KVTransferFormatError("empty export: nothing to ship")
    k0, s0 = _leaf_pair(chunks[0]["k"])
    layout = "kvq" if s0 is not None else "dense"
    header = {
        "version": 1,
        "layout": layout,
        "dtype": k0.dtype.name,
        "chunk_tokens": int(export["chunk_tokens"]),
        "n_chunks": len(chunks),
        "token_ids": [int(t) for t in export["token_ids"]],
        "k_shape": list(k0.shape),
        "logits": [],
        "vocab": 0,
    }
    if layout == "kvq":
        header["scale_dtype"] = s0.dtype.name
        header["s_shape"] = list(s0.shape)
    body = bytearray()
    for ch in chunks:
        logits = ch.get("logits")
        header["logits"].append(logits is not None)
        for leaf in (ch["k"], ch["v"]):
            q, s = _leaf_pair(leaf)
            if (s is not None) != (layout == "kvq"):
                raise KVTransferFormatError("mixed dense/kvq leaves in one export")
            if list(q.shape) != header["k_shape"]:
                raise KVTransferFormatError(
                    f"ragged chunk shape {q.shape} vs {header['k_shape']} (KVX1 "
                    "holds ONE shape for keys and values: a latent (MLA) "
                    "cache's pair of latent and rotary key is not exportable)"
                )
            body += q.tobytes()
            if s is not None:
                body += s.tobytes()
        if logits is not None:
            lg = np.ascontiguousarray(logits, dtype=np.float32).reshape(-1)
            header["vocab"] = int(lg.shape[0])
            body += lg.tobytes()
    hdr = json.dumps(header, sort_keys=True, separators=(",", ":")).encode()
    return MAGIC + struct.pack("<I", len(hdr)) + hdr + bytes(body)


def decode_kv_blob(blob: bytes) -> dict:
    """Parse a blob back into the ``export_prefix_blocks`` dict shape
    (numpy leaves; KVQ chunks come back as ``(codes, scales)`` pairs).
    Raises :class:`KVTransferFormatError` on any malformed input — the
    decode worker treats that as a transfer failure and falls back to
    local prefill rather than importing garbage KV."""
    if len(blob) < len(MAGIC) + 4 or blob[: len(MAGIC)] != MAGIC:
        raise KVTransferFormatError("bad magic: not a KV transfer blob")
    (hlen,) = struct.unpack_from("<I", blob, len(MAGIC))
    off = len(MAGIC) + 4
    if hlen > _MAX_HEADER_BYTES or off + hlen > len(blob):
        raise KVTransferFormatError("header length out of range")
    try:
        header = json.loads(blob[off : off + hlen])
    except ValueError as e:
        raise KVTransferFormatError(f"unparseable header: {e}") from e
    off += hlen
    if header.get("version") != 1:
        raise KVTransferFormatError(f"unknown version {header.get('version')!r}")
    layout = header["layout"]
    if layout not in ("dense", "kvq"):
        raise KVTransferFormatError(f"unknown layout {layout!r}")
    k_shape = tuple(header["k_shape"])
    dtype = _np_dtype(header["dtype"])
    leaf_bytes = int(np.prod(k_shape)) * dtype.itemsize
    if layout == "kvq":
        s_shape = tuple(header["s_shape"])
        s_dtype = _np_dtype(header["scale_dtype"])
        scale_bytes = int(np.prod(s_shape)) * s_dtype.itemsize
    vocab = int(header.get("vocab", 0))

    def take(n: int) -> bytes:
        nonlocal off
        if off + n > len(blob):
            raise KVTransferFormatError("truncated body")
        out = blob[off : off + n]
        off += n
        return out

    chunks = []
    for has_logits in header["logits"]:
        ch: dict = {}
        for name in ("k", "v"):
            q = np.frombuffer(take(leaf_bytes), dtype=dtype).reshape(k_shape)
            if layout == "kvq":
                s = np.frombuffer(take(scale_bytes), dtype=s_dtype).reshape(s_shape)
                ch[name] = (q, s)
            else:
                ch[name] = q
        if has_logits:
            if vocab <= 0:
                raise KVTransferFormatError("logits flagged but vocab missing")
            ch["logits"] = np.frombuffer(
                take(vocab * 4), dtype=np.float32
            ).reshape(vocab)
        else:
            ch["logits"] = None
        chunks.append(ch)
    if len(chunks) != int(header["n_chunks"]):
        raise KVTransferFormatError("chunk count mismatch")
    if off != len(blob):
        raise KVTransferFormatError(f"{len(blob) - off} trailing bytes")
    return {
        "token_ids": list(header["token_ids"]),
        "chunk_tokens": int(header["chunk_tokens"]),
        "chunks": chunks,
    }
