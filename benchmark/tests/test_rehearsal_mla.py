"""The latent-attention / routed-expert / residual-stream family through a
whole run on the CPU (``--rehearse``): a toy configuration of it, the
reference ``references/mla_moe_mhc.py`` and a manifest beside the first one
(``rehearsal/manifest_mla.json``), run to a ``correct`` line. The toy's env
names the Pallas kernel, so the burst program is the one that carries the
expert counters (the absorbed kernel runs in the interpreter)."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = Path(__file__).parent / "rehearsal" / "manifest_mla.json"

# say what the first burst's counters were, where the owner thread sums them
TELL_THE_COUNTERS = """
import json
from nats_llm_studio_tpu.serve import batcher
sound = batcher.BatcherStats.record_moe
def told(self, counters):
    burst = sound(self, counters)
    if self.expert_steps == burst["expert_steps"]:
        print(json.dumps({"first_moe_burst": burst, "shape": list(counters.shape)}), flush=True)
    return burst
batcher.BatcherStats.record_moe = told
"""


def test_the_family_runs_as_files_only_and_its_counters_come_back_with_the_tokens():
    args = ["--workload", "tinymla.toy_closed", "--seed", str(2**31 + 5), "--seconds", "2",
            "--trace", "0", "--manifest", str(MANIFEST), "--rehearse"]
    code = (f"import sys; sys.path.insert(0, {str(ROOT)!r})\n{TELL_THE_COUNTERS}\n"
            f"from benchmark import run\nraise SystemExit(run.main({args!r}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env, timeout=600,
                       capture_output=True, text=True)
    lines = [json.loads(line) for line in p.stdout.splitlines() if line.startswith("{")]
    assert p.returncode == 3, p.stderr[-3000:]
    load = next(x for x in lines if x.get("phase") == "load" and not x.get("begin"))
    ref = next(x for x in lines if x.get("phase") == "reference" and not x.get("begin"))
    assert load["decode_kernel"] == "pallas" and load["weight_bytes"] > 0
    assert ref["ok"] and ref["first_ok"] and ref["decoded"]["ok"] and ref["window"]["ok"], ref
    assert lines[-1]["would_print"]["correct"] is True
    # two expert layers x (experts hit, most rows on one, live rows) rows under
    # the burst's tokens, one column a step
    told = next(x for x in lines if "first_moe_burst" in x)
    burst, (rows, steps) = told["first_moe_burst"], told["shape"]
    assert rows == 2 * 3 and burst["expert_steps"] == 2 * steps
    assert 2 * burst["expert_steps"] >= burst["experts_hit"] >= burst["expert_steps"] > 0
    assert burst["expert_rows"] >= burst["expert_rows_max"] >= burst["expert_steps"]
