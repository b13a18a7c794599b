"""The linear-attention family through a whole run on the CPU
(``--rehearse``): a toy configuration of it, the reference
``references/gdn_moe.py`` and a manifest beside the first one
(``rehearsal/manifest_gdn.json``), added as files only and run to a
``correct`` line: ``start_serve``, the live batcher, the per-slot state pool
beside the paged KV pool, the state kernel, the paged attention kernel and
the expert kernels over a share of the experts (interpreter). The run is
traced, so the three readers of the program's own counters
(``gdn_rows_live_avg``, ``gdn_experts_hit_avg``, ``gdn_picks_held_share``)
read what the window left; the device-trace readers find no device plane on
the CPU and leave their metrics out, as they do on a parent commit. With a
fault put into the decode step underneath the same run (beta left out of the
kernel's call) the line comes out not ``correct``."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = Path(__file__).parent / "rehearsal" / "manifest_gdn.json"

# the decode step writes at full strength whatever beta says; prefill is sound
NO_BETA = """
import jax.numpy as jnp
from nats_llm_studio_tpu.ops import gated_delta
sound = gated_delta.gated_delta_step_auto
def faulty(pool, layer, live, decay, beta, q, k, v):
    return sound(pool, layer, live, decay, jnp.where(beta > 0, 1.0, beta), q, k, v)
gated_delta.gated_delta_step_auto = faulty
"""


def run_toy(before: str = ""):
    args = ["--workload", "tinygdn.toy_closed", "--seed", str(2**31 + 11), "--seconds", "3",
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


def test_the_linear_attention_family_runs_as_files_only_to_a_correct_line():
    lines = run_toy()
    load = next(x for x in lines if x.get("phase") == "load" and not x.get("begin"))
    ref = next(x for x in lines if x.get("phase") == "reference" and not x.get("begin"))
    assert load["decode_kernel"] == "pallas" and load["max_slots"] == 4
    assert ref["ok"] and ref["first_ok"] and ref["decoded"]["ok"] and ref["window"]["ok"], ref
    out = lines[-1]["would_print"]
    assert out["correct"] is True and out["failed"] == 0
    # three callers on four slots; the toy's silent router takes experts 0-3 of
    # 32 for every token, of which rank 1 of 4 holds one (expert 1): a quarter
    # of the picks, one expert a layer a step
    rows = out["metrics"]["gdn_rows_live_avg"]["value"]
    assert 1.0 <= rows <= 4.0, out["metrics"]
    assert out["metrics"]["gdn_experts_hit_avg"]["value"] == pytest.approx(1.0)
    assert out["metrics"]["gdn_picks_held_share"]["value"] == pytest.approx(25.0)
    unread = next((x["metrics"] for x in lines if x.get("phase") == "unread"), [])
    assert not {"gdn_rows_live_avg", "gdn_experts_hit_avg", "gdn_picks_held_share"} & set(unread)
    assert {"gdn_state_step_roofline", "gdn_decode_step_roofline", "gdn_seq_linear_share",
            "gdn_prefill_chunk_ms", "gdn_prefill_chunk_mfu"} <= set(unread)


def test_a_fault_in_the_decode_step_comes_out_not_correct():
    lines = run_toy(NO_BETA)
    ref = next(x for x in lines if x.get("phase") == "reference" and not x.get("begin"))
    assert ref["first_ok"] and not ref["decoded"]["ok"], ref   # prefill is sound, decode is not
    assert lines[-1]["would_print"]["correct"] is False
