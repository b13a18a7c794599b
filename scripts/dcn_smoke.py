"""Multi-process (DCN-path) smoke: proves `jax.distributed.initialize` +
cross-process mesh actually RUN, not just parse env vars (VERDICT r3 #8).

Two local processes, CPU backend, 4 virtual devices each, one coordinator:
build a global dp=2 x tp=4 mesh spanning both processes, run (a) a psum
over dp inside shard_map and (b) one jitted tiny-llama forward with the
batch dp-sharded and the KV cache sharding-constrained onto the mesh — the
same SPMD program shape `main.py`'s `jax.distributed.initialize` hook
(NATS control plane + XLA collectives tensor plane, SURVEY.md §5) promises
for multi-host. On real multi-host TPU the only change is the coordinator
address and device count; the program is identical.

Usage:
  python scripts/dcn_smoke.py            # launcher: spawns 2 workers
  python scripts/dcn_smoke.py worker N P # internal: worker N, coord port P
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
from pathlib import Path

REPO = str(Path(__file__).resolve().parent.parent)


def worker(pid: int, port: str) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    )
    import jax

    # where jax was imported before the env var was set the variable is
    # too late — force the platform through the config API too (same
    # recipe as tests/conftest.py). These children are CPU-only by
    # construction: a chip belongs to one process.
    jax.config.update("jax_platforms", "cpu")

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
    )
    assert len(jax.local_devices()) == 4, jax.local_devices()
    assert len(jax.devices()) == 8, "global device view must span both processes"

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    sys.path.insert(0, REPO)
    from nats_llm_studio_tpu.models.config import ModelConfig
    from nats_llm_studio_tpu.models.llama import forward, init_params, make_cache
    from nats_llm_studio_tpu.parallel import build_mesh

    mesh = build_mesh("dp=2,tp=4")  # 8 global devices, 4 per process

    # (a) cross-process collective: psum over the dp axis
    from jax import shard_map

    f = jax.jit(
        shard_map(
            lambda x: jax.lax.psum(x, "dp"),
            mesh=mesh,
            in_specs=P("dp", None),
            out_specs=P(None, None),
        ),
        in_shardings=NamedSharding(mesh, P("dp", None)),
    )
    x = jnp.ones((2, 4), jnp.float32)
    out = f(x)
    local = np.asarray(out.addressable_shards[0].data)
    assert np.allclose(local, 2.0), local  # dp=2 ranks of ones summed
    print(f"PSUM_OK {pid}", flush=True)

    # (b) one tiny sharded forward: batch on dp, cache constrained on-mesh
    cfg = ModelConfig.tiny(n_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0))  # deterministic, replicated
    tokens = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)

    @jax.jit
    def step(params, tokens):
        k, v = make_cache(cfg, 2, 16)
        k = jax.lax.with_sharding_constraint(k, NamedSharding(mesh, P("dp")))
        v = jax.lax.with_sharding_constraint(v, NamedSharding(mesh, P("dp")))
        logits, _, _ = forward(
            params, cfg, tokens, k, v, jnp.zeros((2,), jnp.int32)
        )
        return jax.lax.with_sharding_constraint(logits, NamedSharding(mesh, P()))

    logits = step(params, tokens)
    arr = np.asarray(logits.addressable_shards[0].data)
    assert np.all(np.isfinite(arr))
    # both processes must compute identical replicated logits
    print(f"LOGITS_SUM {pid} {float(np.abs(arr).sum()):.6f}", flush=True)
    jax.distributed.shutdown()


def launch() -> int:
    with socket.socket() as s:  # free port for the coordinator
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    procs = [
        subprocess.Popen(
            [sys.executable, __file__, "worker", str(i), port],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env={k: v for k, v in os.environ.items()
                 if k not in ("XLA_FLAGS", "JAX_PLATFORMS")},
        )
        for i in range(2)
    ]
    outs = [p.communicate(timeout=300)[0] for p in procs]
    ok = all(p.returncode == 0 for p in procs)
    sums = []
    for i, out in enumerate(outs):
        print(f"--- worker {i} ---\n{out}")
        if f"PSUM_OK {i}" not in out:
            ok = False
        for line in out.splitlines():
            if line.startswith("LOGITS_SUM"):
                sums.append(line.split()[-1])
    if len(sums) != 2 or sums[0] != sums[1]:
        ok = False  # replicated forward diverged across processes
    print("DCN_SMOKE", "PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "worker":
        worker(int(sys.argv[2]), sys.argv[3])
    else:
        sys.exit(launch())
