"""The hit list through the live batcher, on the CPU: the toy of the
latent-attention / routed-expert family (``benchmark/tests/rehearsal``)
served on TWO slots, so that a decode burst has 2 rows x top-2 = 4 picks
under its 8 experts and ``decode_pos_moe`` takes the hit list (the Pallas
interpreter runs the kernel's own code), held to the plain reference by the
run's own ``correct``. On the toy's four slots (``benchmark/tests/
test_rehearsal_mla.py``) the same burst is dense dispatch."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
MANIFEST = ROOT / "benchmark/tests/rehearsal/manifest_mla.json"

# say what each burst's readback span will carry, where the owner thread sums it
TELL_THE_BURSTS = """
import json
from nats_llm_studio_tpu.serve import batcher
sound = batcher.BatcherStats.record_moe
def told(self, counters):
    burst = sound(self, counters)
    print(json.dumps({"moe_burst": burst}), flush=True)
    return burst
batcher.BatcherStats.record_moe = told
"""


def test_a_burst_of_two_slots_takes_the_hit_list_and_stays_correct():
    args = ["--workload", "tinymla.toy_closed", "--seed", str(2**31 + 7), "--seconds", "2",
            "--trace", "0", "--manifest", str(MANIFEST), "--rehearse",
            "--env", "MAX_BATCH_SLOTS=2"]
    code = (f"import sys; sys.path.insert(0, {str(ROOT)!r})\n{TELL_THE_BURSTS}\n"
            f"from benchmark import run\nraise SystemExit(run.main({args!r}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env, timeout=900,
                       capture_output=True, text=True)
    lines = [json.loads(line) for line in p.stdout.splitlines() if line.startswith("{")]
    assert p.returncode == 3, p.stderr[-3000:]
    ref = next(x for x in lines if x.get("phase") == "reference" and not x.get("begin"))
    assert ref["ok"] and ref["first_ok"] and ref["decoded"]["ok"] and ref["window"]["ok"], ref
    assert lines[-1]["would_print"]["correct"] is True
    bursts = [x["moe_burst"] for x in lines if "moe_burst" in x]
    assert bursts and all(b["expert_path"] == "hit_list" for b in bursts)
    # 2 rows x top-2: a step and layer reads at most 4 of the 8 experts, and
    # none where no slot holds a request
    assert all(0 <= b["experts_hit"] <= 4 * b["expert_steps"] for b in bursts)
    assert any(b["experts_hit"] >= 2 * b["expert_steps"] > 0 for b in bursts)
