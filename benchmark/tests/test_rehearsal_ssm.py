"""The state-space / attention hybrid family through a whole run on the CPU
(``--rehearse``): a toy configuration of it, the reference
``references/ssm_hybrid.py`` and a manifest beside the first one
(``rehearsal/manifest_ssm.json``), added as files only and run to a
``correct`` line: ``start_serve``, the live batcher, the per-slot state pool
beside the paged KV pool, packed kv rows of head_dim 64 and both Pallas
kernels (interpreter). The run is traced, so the two readers of the program's
own counters (``ssm_rows_live_avg``, ``ssm_state_pool_used_share``) read
what the window left; the device-trace readers find no device plane on the
CPU and leave their metrics out, as they do on a parent commit."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = Path(__file__).parent / "rehearsal" / "manifest_ssm.json"


def test_the_state_space_family_runs_as_files_only_to_a_correct_line():
    args = ["--workload", "tinyssm.toy_closed", "--seed", str(2**31 + 5), "--seconds", "3",
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
    # four callers on four slots: between one and four rows advance their
    # state a step; the pool's share is three samples of a 3 s window (under
    # six test workers one of them read 12.5 %: its presence is the check)
    rows = out["metrics"]["ssm_rows_live_avg"]["value"]
    share = out["metrics"]["ssm_state_pool_used_share"]["value"]
    assert 1.0 <= rows <= 4.0 and 0.0 <= share <= 100.0, out["metrics"]
