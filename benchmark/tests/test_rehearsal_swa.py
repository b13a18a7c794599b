"""The window / full attention family through a whole run on the CPU
(``--rehearse``): a toy configuration of it, the reference
``references/swa_gated_moe.py`` and a manifest beside the first one
(``rehearsal/manifest_swa.json``), added as files only and run to a
``correct`` line: ``start_serve``, the live batcher, the per-slot rings
beside the paged pool of the full layers, both attention kernels and the
expert kernels (interpreter). The toy's window is 16 and its prompts are 30
and more, so every ring has wrapped before the first decoded position the
check compares. The run is traced, so the two readers of the program's own
counters (``swa_kv_window_share``, ``swa_experts_hit_avg``) read what the
window left; the device-trace readers find no device plane on the CPU and
leave their metrics out, as they do on a parent commit."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = Path(__file__).parent / "rehearsal" / "manifest_swa.json"


def test_the_window_family_runs_as_files_only_to_a_correct_line():
    args = ["--workload", "tinyswa.toy_closed", "--seed", str(2**31 + 7), "--seconds", "3",
            "--trace", "1", "--manifest", str(MANIFEST), "--rehearse"]
    code = (f"import sys; sys.path.insert(0, {str(ROOT)!r})\n"
            f"from benchmark import run\nraise SystemExit(run.main({args!r}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env, timeout=600,
                       capture_output=True, text=True)
    lines = [json.loads(line) for line in p.stdout.splitlines() if line.startswith("{")]
    assert p.returncode == 3, p.stderr[-3000:]
    load = next(x for x in lines if x.get("phase") == "load" and not x.get("begin"))
    ref = next(x for x in lines if x.get("phase") == "reference" and not x.get("begin"))
    assert load["decode_kernel"] == "pallas" and load["max_slots"] == 4
    assert ref["ok"] and ref["first_ok"] and ref["decoded"]["ok"] and ref["window"]["ok"], ref
    out = lines[-1]["would_print"]
    assert out["correct"] is True and out["failed"] == 0
    # contexts of 30-112 against a window of 16: the window layers read 13-35 %
    # of the keys a layer of each kind reads; a step's 1-3 live rows of top-2
    # hit 2-6 of the 16 experts
    share = out["metrics"]["swa_kv_window_share"]["value"]
    hit = out["metrics"]["swa_experts_hit_avg"]["value"]
    assert 10.0 <= share <= 40.0 and 2.0 <= hit <= 6.0, out["metrics"]
    unread = next((x["metrics"] for x in lines if x.get("phase") == "unread"), [])
    assert "swa_kv_window_share" not in unread and "swa_experts_hit_avg" not in unread
