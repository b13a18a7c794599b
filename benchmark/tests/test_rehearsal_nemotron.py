"""The state-space family with a layer one sublayer (Mamba-2 of two groups,
NoPE attention, a share of two-matrix relu^2 experts in a latent) through a
whole run on the CPU (``--rehearse``): a toy configuration of it, the
reference ``references/ssm_latent_moe.py`` and a manifest beside the first one
(``rehearsal/manifest_nemotron.json``), added as files only and run to a
``correct`` line: ``start_serve``, the live batcher, the per-slot state pool
beside the paged KV pool, the state kernel at two groups, the paged attention
kernel and the two-matrix expert kernels over a share of the experts
(interpreter). The run is traced, so the four readers of the program's own
counters (``lmoe_rows_live_avg``, ``lmoe_experts_hit_avg``,
``lmoe_picks_held_share``, ``lmoe_state_pool_used_share``) read what the
window left; the device-trace readers find no device plane on the CPU and
leave their metrics out, as they do on a parent commit. With a fault put into
the decode step underneath the same run (every head reads group 0's B and C
in the state kernel) the line comes out not ``correct``."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = Path(__file__).parent / "rehearsal" / "manifest_nemotron.json"

# the decode step's state kernel gives every head group 0's B and C; prefill is sound
GROUP_0_FOR_EVERY_HEAD = """
import jax.numpy as jnp
from nats_llm_studio_tpu.ops import ssm_scan
sound = ssm_scan.ssm_state_step_auto
def faulty(pool, layer, live, decay, dtx, bm, cm):
    first = lambda z: jnp.broadcast_to(z[:, :1], z.shape)
    return sound(pool, layer, live, decay, dtx, first(bm), first(cm))
ssm_scan.ssm_state_step_auto = faulty
"""


def run_toy(before: str = ""):
    args = ["--workload", "tinynemotron.toy_closed", "--seed", str(2**31 + 17), "--seconds", "3",
            "--trace", "1", "--manifest", str(MANIFEST), "--rehearse"]
    code = (f"import sys; sys.path.insert(0, {str(ROOT)!r})\n{before}\n"
            f"from benchmark import run\nraise SystemExit(run.main({args!r}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env, timeout=900,
                       capture_output=True, text=True)
    lines = [json.loads(line) for line in p.stdout.splitlines() if line.startswith("{")]
    assert p.returncode == 3, p.stderr[-3000:]
    return lines


def test_the_latent_expert_family_runs_as_files_only_to_a_correct_line():
    lines = run_toy()
    load = next(x for x in lines if x.get("phase") == "load" and not x.get("begin"))
    ref = next(x for x in lines if x.get("phase") == "reference" and not x.get("begin"))
    assert load["decode_kernel"] == "pallas" and load["max_slots"] == 4
    assert ref["ok"] and ref["first_ok"] and ref["decoded"]["ok"] and ref["window"]["ok"], ref
    out = lines[-1]["would_print"]
    assert out["correct"] is True and out["failed"] == 0
    # three callers on four slots; a LIVE router: one to four rows of top-6
    # over 32 experts hit between one and eight of the eight held here, and
    # about a quarter of the picks land here
    rows = out["metrics"]["lmoe_rows_live_avg"]["value"]
    assert 1.0 <= rows <= 4.0, out["metrics"]
    assert 0.5 <= out["metrics"]["lmoe_experts_hit_avg"]["value"] <= 8.0
    assert 5.0 <= out["metrics"]["lmoe_picks_held_share"]["value"] <= 60.0
    assert 0.0 <= out["metrics"]["lmoe_state_pool_used_share"]["value"] <= 100.0
    unread = next((x["metrics"] for x in lines if x.get("phase") == "unread"), [])
    assert not {"lmoe_rows_live_avg", "lmoe_experts_hit_avg", "lmoe_picks_held_share",
                "lmoe_state_pool_used_share"} & set(unread)
    assert {"lmoe_state_step_roofline", "lmoe_decode_step_roofline", "lmoe_experts_roofline",
            "lmoe_prefill_chunk_ms", "lmoe_prefill_chunk_mfu"} <= set(unread)


def test_a_dropped_group_in_the_decode_step_comes_out_not_correct():
    lines = run_toy(GROUP_0_FOR_EVERY_HEAD)
    ref = next(x for x in lines if x.get("phase") == "reference" and not x.get("begin"))
    # a probe's first token is its last prompt position replayed through the
    # decode step (it asks for log-probabilities), so it reads the wrong group too
    assert not ref["decoded"]["ok"] and ref["decoded"]["gap_max"] > 6.0, ref
    assert lines[-1]["would_print"]["correct"] is False
