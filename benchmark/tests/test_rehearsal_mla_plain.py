"""The plain latent-attention family (one residual stream, one query matrix,
two shared experts) through a whole run on the CPU (``--rehearse``): a toy
configuration of it, the reference ``references/mla_moe_plain.py`` and a
manifest beside the first one (``rehearsal/manifest_mla_plain.json``), added
as files only and run to a ``correct`` line over first and decoded positions.
The toy's env names the Pallas kernel, so the burst program is the one that
carries the expert counters and ``live_tokens`` (the absorbed kernel runs in
the interpreter)."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = Path(__file__).parent / "rehearsal" / "manifest_mla_plain.json"

# say what the decode bursts and the chunk launches left in the span ring
TELL_THE_SPANS = """
import atexit, json
def told():
    from nats_llm_studio_tpu.obs import spans
    burst = [a for _, _, _, a in spans.records(0.0, float("inf"), "batcher.readback")
             if a and "live_tokens" in a]
    chunk = [a for _, _, _, a in spans.records(0.0, float("inf"), "batcher.admit")
             if a and a.get("program") == "chunk"]
    print(json.dumps({"bursts": len(burst), "live_tokens": sum(a["live_tokens"] for a in burst),
                      "chunks": len(chunk), "chunk_tokens": sum(a["tokens"] for a in chunk)}),
          flush=True)
atexit.register(told)
"""


def test_the_plain_latent_family_runs_as_files_only_to_a_correct_line():
    args = ["--workload", "tinymlaplain.toy_wide_closed", "--seed", str(2**31 + 44), "--seconds", "2",
            "--trace", "0", "--manifest", str(MANIFEST), "--rehearse"]
    code = (f"import sys; sys.path.insert(0, {str(ROOT)!r})\n{TELL_THE_SPANS}\n"
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
    assert ref["decoded"]["positions"] > 0 and ref["window"]["positions"] > 0
    assert next(x for x in lines if "would_print" in x)["would_print"]["correct"] is True
    told = next(x for x in lines if "live_tokens" in x)
    assert told["bursts"] > 0 and told["live_tokens"] > 0
