"""Seeded weights made on the device, and the one program name the benchmark
overrides to serve them.

``LocalRegistry._load`` looks ``parallel.loader.load_params_sharded`` up at
call time; ``install(seed)`` sets that module attribute to ``seeded_params``,
which builds the tree the program would load in ONE jitted call from the
seed. Nothing touches the host and no GGUF tensor is read: the models dir
holds a header-only file.

The SCHEMA is the program's: ``jax.eval_shape`` of its own initialiser
(``program_param_shapes``), or of the initialiser a reference module names
for its family (``param_shapes(mcfg)`` in ``references/<name>.py``). Every
leaf is then made by rules on its name and shape alone (``leaf_rule``), so a
family with leaves this file has never seen is a new reference file and a new
program function, and no edit here:

    rank-1 per layer, name ends in ``norm``   ones
    ``lm_head``                               N(0, INIT_STD), printable-ASCII
                                              columns loud (ASCII_LOGIT_STD)
    every other float leaf                    N(0, INIT_STD x gain), rounded
                                              to the serving dtype; gain is
                                              QK_GAIN for ``wq``/``wk``, 1
                                              otherwise, or what the
                                              reference module's
                                              ``weight_gains`` gives
    int8 under quant="int8"                   where ``ops.wquant.quantizable``
                                              says so
    sharding                                  ``parallel.sharding.
                                              param_sharding_rules``; a leaf
                                              without a rule is an error

Leaves under ``blocks`` are stacked on a leading layer axis and drawn one
layer slice at a time, so the float32 transient stays one slice.

The substitution and the schema fail loudly. If a program name they need is
gone or its signature changed, they raise; there is no fallback to a GGUF
load or to a list of leaves kept here.

Copied, not imported: the int8 coding of ``ops.wquant.quantize_weight`` and
the printable-ASCII-loud head of ``chip_smoke.py:112-117``.
"""

from __future__ import annotations

import inspect
import time
import zlib

# every N(0, INIT_STD) like models.llama.init_params
INIT_STD = 0.02
# the head's printable-ASCII columns (token id == byte value under the
# byte-level tokenizer) are scaled so that their logits have about this
# standard deviation AFTER the configuration's logit scale: chip_smoke.py's
# 8x at d 4096 with no logit scale is 0.02 * 64 * 8 = 10.2. The random model
# then emits printable bytes like a trained byte-level model: streams deliver
# chunk by chunk, and the eos id is practically never sampled (its logit is
# ~0 against a printable maximum of ~24), so every reply runs to max_tokens.
ASCII_LOGIT_STD = 10.0
# wq and wk are drawn this much louder. At N(0, 0.02) the attention scores of
# these widths have a spread of 0.1-0.15, every softmax is flat, attention
# returns the mean of the values and adds almost nothing to the stream: a
# wrong kv head, a wrong block table or a wrong mask would then barely move
# the logits the reference check reads. x4 on both sides puts the scores'
# spread near 2, as peaked as a trained model's. A reference module's
# ``weight_gains`` does the same for the gates or routers of its family.
QK_GAIN = 4.0
DEFAULT_GAIN = {"wq": QK_GAIN, "wk": QK_GAIN}
ASCII_LO, ASCII_HI = 32, 127  # [lo, hi)

# The draw of the leaves the first benchmark had, so that its cells' trees
# stay bit for bit what they were: ``fold_in(k_blocks, i)``. Any other leaf
# folds in a hash of its path. Where two leaves of one tree claim one number,
# the one named first here keeps it and the other takes its hash.
FIRST_DRAWS = {"wq": 0, "wk": 1, "wv": 2, "wo": 3,
               "w_gate": 4, "w_up": 5, "w_down": 6,
               "router": 4, "w_gate_e": 5, "w_up_e": 6, "w_down_e": 7}
STACKED = "blocks."  # leaves under it carry a leading layer axis

EXPECTED_SIGNATURE = ("reader", "cfg", "mesh", "dtype", "quant", "group")


def ascii_column_scale(cfg) -> float:
    plain = INIT_STD * (cfg.d_model ** 0.5) * cfg.logit_scale
    return ASCII_LOGIT_STD / plain


def _seed_key(seed: int):
    import jax

    # --seed may exceed 32 signed bits: split it instead of truncating
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 31)


def program_param_shapes(cfg):
    """The tree the program would load for ``cfg``, as shapes: its own
    initialiser with the head materialised, never run."""
    import jax

    from nats_llm_studio_tpu.models import llama

    init = getattr(llama, "init_params", None)
    ensure = getattr(llama, "ensure_lm_head", None)
    if init is None or ensure is None:
        raise RuntimeError(
            "models.llama.init_params / ensure_lm_head is gone: the seeded "
            "weights take their schema from it (see benchmark/README.md); name "
            "the program's initialiser here or in the reference's param_shapes")
    return jax.eval_shape(lambda: ensure(init(cfg, jax.random.PRNGKey(0))))


def flatten(tree: dict, prefix: str = "") -> dict:
    """{dotted path: leaf} of nested dicts."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, f"{prefix}{k}."))
        else:
            out[f"{prefix}{k}"] = v
    return out


def unflatten(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, last = path.split(".")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = v
    return out


def leaf_rule(path: str, shape: tuple) -> str:
    """How one leaf is made, from its name and shape: "ones", "head" or
    "normal"."""
    name = path.rsplit(".", 1)[-1]
    per_layer = shape[1:] if path.startswith(STACKED) else shape
    if name.endswith("norm") and len(per_layer) == 1:
        return "ones"
    return "head" if path == "lm_head" else "normal"


def path_number(path: str) -> int:
    """A stable 31-bit number for a leaf's path, clear of FIRST_DRAWS."""
    return (zlib.crc32(path.encode()) & 0x3FFFFFFF) | 0x40000000


def draw_numbers(paths: list[str]) -> dict[str, int]:
    """The number each stacked leaf folds into the blocks' key."""
    out: dict[str, int] = {}
    taken: set[int] = set()
    names = {p[len(STACKED):]: p for p in paths}
    for name, i in FIRST_DRAWS.items():
        if name in names and i not in taken:
            out[names[name]] = i
            taken.add(i)
    for p in paths:
        out.setdefault(p, path_number(p))
    return out


def by_leaf(asked: dict, paths: list[str], what: str, optional=()) -> dict:
    """{path: value}; a key is a leaf's dotted path or its last name. A key
    that names no leaf of the tree is an error (but for the ``optional``)."""
    out = {}
    for key, v in asked.items():
        hits = [p for p in paths if p == key or p.rsplit(".", 1)[-1] == key]
        if not hits and key not in optional:
            raise ValueError(f"{what} names {key!r}, which is no leaf of {sorted(paths)}")
        out.update(dict.fromkeys(hits, v))
    return out


def resolve_gains(gain: dict | None, paths: list[str]) -> dict[str, float]:
    asked = {k: float(g) for k, g in (DEFAULT_GAIN | dict(gain or {})).items()}
    return by_leaf(asked, paths, "weight_gains", optional=DEFAULT_GAIN)


def make_seeded_params(seed: int, family=None, fixed_draws: dict | None = None):
    """Returns a function with the loader's signature that ignores the
    reader's tensors and builds the tree from ``seed``. ``family`` is the
    configuration's reference module (or anything with its two optional
    names): ``param_shapes(cfg)`` gives the schema (default:
    ``program_param_shapes``), ``weight_gains`` a {leaf: factor} beside it.
    ``fixed_draws`` is the configuration's own {leaf: number}: such a leaf is
    drawn from that number and not from ``seed``, so every seed serves the
    SAME leaf. For the leaves that decide how much work a step is (a router
    and its selection bias pick how many experts a step streams: six seeds
    spread 2.5 % in tokens/s by that alone, PERF.md PR 39); every other leaf,
    the texts and the sampling seeds stay the seed's.
    Its ``last_build`` attribute holds the seconds and bytes of its last
    call."""
    param_shapes = getattr(family, "param_shapes", None)
    gain = getattr(family, "weight_gains", None)

    def seeded_params(reader, cfg, mesh, dtype=None, quant="none", group=32):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from nats_llm_studio_tpu.ops.wquant import QTensor, quantizable
        from nats_llm_studio_tpu.parallel.sharding import (
            param_sharding_rules, scale_spec)

        if quant not in ("none", "int8"):
            raise NotImplementedError(
                f"seeded weights cover quant 'none' and 'int8', not {quant!r}")
        t0 = time.perf_counter()
        dt = jnp.dtype(dtype or cfg.dtype)
        schema = flatten((param_shapes or program_param_shapes)(cfg))
        for path, x in schema.items():
            if not jnp.issubdtype(x.dtype, jnp.floating):
                raise NotImplementedError(
                    f"leaf {path!r} is {x.dtype}: seeded weights have rules for float leaves only")
        rules = param_sharding_rules(mesh, cfg)
        missing = sorted(p for p in schema if p not in rules)
        if missing:
            raise KeyError(
                f"parallel.sharding.param_sharding_rules has no rule for {missing}: "
                "the program cannot place a leaf its own initialiser makes")
        stacked = [p for p in schema if p.startswith(STACKED)]
        draws = draw_numbers(stacked)
        gains = resolve_gains(gain, list(schema))
        fixed = by_leaf(dict(fixed_draws or {}), list(schema), "fixed_draws")
        col = ascii_column_scale(cfg)

        def q8(w):
            # the math of ops.wquant.quantize_weight: symmetric absmax int8
            # over the contraction (second-to-last) axis
            wf = w.astype(jnp.float32)
            amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
            s = amax / 127.0
            safe = jnp.where(s == 0, 1.0, s)
            q = jnp.clip(jnp.round(wf / safe), -127, 127).astype(jnp.int8)
            return QTensor(q=q, s=safe.astype(jnp.float32))

        def coded(path, w):
            return q8(w) if quant == "int8" and quantizable(path) else w.astype(dt)

        def randn(k, shape, gain=1.0):
            # rounded to the serving dtype first, as a loaded bf16 file is
            return (jax.random.normal(k, shape, jnp.float32) * (INIT_STD * gain)).astype(dt)

        def head(k, shape):
            # drawn on its own even where the published model ties it to the
            # embedding: a random tied head gives the last prompt token's own
            # column a logit of ~12 |e|^2 x the loudness (50 at these
            # widths), and the model repeats one byte for ever. The served
            # tree holds a materialised lm_head either way.
            v = shape[-1]
            loud = jnp.where(
                (jnp.arange(v) >= ASCII_LO) & (jnp.arange(v) < ASCII_HI), col, 1.0)
            return randn(k, shape).astype(jnp.float32) * loud[None, :]

        def build(key):
            k_embed, k_head, k_seed_blocks = jax.random.split(key, 3)
            top_keys = {"embed": k_embed, "lm_head": k_head}
            out = {}
            for path, x in schema.items():
                rule = leaf_rule(path, x.shape)
                g = gains.get(path, 1.0)
                # a leaf the configuration pins: the blocks' key of ITS number
                k_blocks = (jax.random.split(_seed_key(int(fixed[path])), 3)[2]
                            if path in fixed else k_seed_blocks)
                if rule == "ones":
                    out[path] = jnp.ones(x.shape, dt)
                elif path in draws:
                    keys = jax.random.split(jax.random.fold_in(k_blocks, draws[path]), x.shape[0])
                    # one layer slice at a time: the f32 transient is one slice
                    out[path] = jax.lax.map(
                        lambda k, path=path, shape=x.shape[1:], g=g: coded(
                            path, randn(k, shape, g)), keys)
                else:
                    k = top_keys.get(path)
                    if k is None:
                        k = jax.random.fold_in(k_blocks, path_number(path))
                    out[path] = coded(path, head(k, x.shape) if rule == "head"
                                      else randn(k, x.shape, g))
            return unflatten(out)

        def placed(path, x):
            spec = rules[path]
            if isinstance(x, QTensor):
                if path in draws:
                    s_spec = P(spec[0], *scale_spec(P(*spec[1:])))
                else:
                    s_spec = scale_spec(spec)
                return QTensor(q=NamedSharding(mesh, spec), s=NamedSharding(mesh, s_spec))
            return NamedSharding(mesh, spec)

        built = flatten(jax.eval_shape(build, _seed_key(seed)))
        out_shardings = unflatten({p: placed(p, x) for p, x in built.items()})
        params = jax.jit(build, out_shardings=out_shardings)(_seed_key(seed))
        jax.block_until_ready(params)
        seeded_params.last_build.update(
            seconds=time.perf_counter() - t0, seed=seed, quant=quant,
            bytes=sum(x.nbytes for x in jax.tree.leaves(params)))
        return params

    seeded_params.last_build = {}
    return seeded_params


def install(seed: int, family=None, fixed_draws: dict | None = None):
    """Point ``parallel.loader.load_params_sharded`` at the seeded builder
    and return it. The only program name the benchmark overrides."""
    from nats_llm_studio_tpu.parallel import loader

    target = getattr(loader, "load_params_sharded", None)
    if target is None:
        raise RuntimeError(
            "parallel.loader.load_params_sharded is gone: the benchmark's one "
            "seam for seeded weights needs a new home (see benchmark/README.md)")
    names = tuple(inspect.signature(target).parameters)
    if names != EXPECTED_SIGNATURE:
        raise RuntimeError(
            f"parallel.loader.load_params_sharded{names} no longer matches "
            f"{EXPECTED_SIGNATURE}: refusing to substitute seeded weights")
    loader.load_params_sharded = builder = make_seeded_params(seed, family, fixed_draws)
    return builder
