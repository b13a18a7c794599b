"""The lightning / block-sparse family through a whole run on the CPU
(``--rehearse``): a toy configuration of it, the reference
``references/sala.py`` and a manifest beside the first one
(``rehearsal/manifest_sala.json``), added as files only and run to a
``correct`` line: ``start_serve``, the live batcher, the per-slot state pool
and the slots' pooled keys beside the paged KV pool, the state kernel and the
picked walk (interpreter). Every prompt of the toy mix is past the toy's dense
length and its median past one prefill chunk, so the long probe holds the
masked prefill, the pooled-key cache and the picked walk to the reference.
The run is traced, so the two readers of the program's own counters
(``sala_picked_share``, ``sala_rows_live_avg``) read what the window left; the
device-trace readers find no device plane on the CPU and leave their metrics
out, as they do on a parent commit. With a fault put into the decode step
underneath the same run (the state kernel is called without its decay) the line
comes out not ``correct``."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = Path(__file__).parent / "rehearsal" / "manifest_sala.json"

# the decode step's state never decays; prefill is sound. (A fault in the
# SELECTION moves the toy's logits by ~1.5 at most under the harness's unit q / k
# norm gains, inside the cell's limits: tests/test_sala_faults.py holds those,
# with loud gains, to the toy limits)
NO_DECAY = """
import jax.numpy as jnp
from nats_llm_studio_tpu.ops import lightning
sound = lightning.lightning_step_auto
def faulty(pool, layer, live, decay, q, k, v):
    return sound(pool, layer, live, jnp.ones_like(decay), q, k, v)
lightning.lightning_step_auto = faulty
"""


def run_toy(before: str = ""):
    args = ["--workload", "tinysala.toy_closed", "--seed", str(2**31 + 11), "--seconds", "3",
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


def test_the_lightning_family_runs_as_files_only_to_a_correct_line():
    lines = run_toy()
    load = next(x for x in lines if x.get("phase") == "load" and not x.get("begin"))
    probes = next(x for x in lines if x.get("phase") == "probes" and not x.get("begin"))
    ref = next(x for x in lines if x.get("phase") == "reference" and not x.get("begin"))
    assert load["decode_kernel"] == "pallas" and load["max_slots"] == 4
    # the fourth probe is the mix's median prompt: past one chunk of 256 and the dense length
    assert len(probes["prompt_tokens"]) == 4 and probes["prompt_tokens"][3] > 256
    assert ref["ok"] and ref["first_ok"] and ref["decoded"]["ok"] and ref["window"]["ok"], ref
    out = lines[-1]["would_print"]
    assert out["correct"] is True and out["failed"] == 0
    # three callers on four slots; every context between 200 and 452 keys of
    # which a sparse layer walks 4 whole blocks of 16 and its frontier block
    assert 1.0 <= out["metrics"]["sala_rows_live_avg"]["value"] <= 4.0, out["metrics"]
    assert 15.0 < out["metrics"]["sala_picked_share"]["value"] < 40.0, out["metrics"]
    unread = next((x["metrics"] for x in lines if x.get("phase") == "unread"), [])
    assert not {"sala_rows_live_avg", "sala_picked_share"} & set(unread)
    assert {"sala_picked_walk_roofline", "sala_state_step_roofline", "sala_decode_step_roofline",
            "sala_select_ms_per_step", "sala_seq_share", "sala_prefill_chunk_ms",
            "sala_prefill_chunk_mfu"} <= set(unread)


def test_a_fault_in_the_state_step_comes_out_not_correct():
    lines = run_toy(NO_DECAY)
    ref = next(x for x in lines if x.get("phase") == "reference" and not x.get("begin"))
    assert ref["first_ok"] and not ref["decoded"]["ok"], ref   # prefill is sound, decode is not
    assert lines[-1]["would_print"]["correct"] is False
