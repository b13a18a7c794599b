"""The whole of a run on the CPU (``--rehearse``): through ``start_serve``, the
live batcher, the probes, the window and the reference check, at a toy size.
Each run is a process of its own, as the driver's are, and takes about half a
minute."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = Path(__file__).parent / "rehearsal" / "manifest.json"
ARGS = ["--seed", str(2**31 + 5), "--seconds", "2", "--trace", "0",
        "--manifest", str(MANIFEST), "--rehearse"]

# the timed path broken underneath: every token is altered where it is
# produced (the sampler's choice plus one), in the prefill and in the decode
BREAK_THE_SAMPLER = """
import jax.numpy as jnp
from nats_llm_studio_tpu.serve import batcher
sound = batcher.sample_rows
def altered(*a, **kw):
    tok = sound(*a, **kw)
    return jnp.where(tok < 126, tok + 1, tok - 1).astype(tok.dtype)
batcher.sample_rows = altered
"""

# ... and in the burst decode program alone, the one the window times: a
# request with logprobs (every probe) samples through the ``_ext`` program,
# which passes a mask; the burst program and the plain admits pass none
BREAK_THE_BURST = BREAK_THE_SAMPLER.replace(
    "tok = sound(*a, **kw)", "tok = sound(*a, **kw)\n    if 'mask' in kw: return tok")


def rehearse(workload: str, before: str = "", extra: tuple = ()) -> tuple[int, list[dict], str]:
    """Run one cell of the rehearsal manifest; (exit code, stdout's JSON
    lines, the end of stderr)."""
    code = (f"import sys; sys.path.insert(0, {str(ROOT)!r})\n{before}\n"
            "from benchmark import run\n"
            f"raise SystemExit(run.main({['--workload', workload] + ARGS + list(extra)!r}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)   # one CPU device, as the driver's run has one chip
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env, timeout=600,
                       capture_output=True, text=True)
    lines = [json.loads(line) for line in p.stdout.splitlines() if line.startswith("{")]
    return p.returncode, lines, p.stderr[-3000:]


@pytest.fixture(scope="module")
def sound():
    # with the two flags only a builder's control runs give: the reference a
    # precision lower in the served path's place, and a serving env overridden
    return rehearse("tiny.toy_closed", extra=("--control", "fp8", "--env", "MAX_BATCH_SLOTS=3"))


def test_the_sound_path_is_correct_through_the_live_batcher(sound):
    code, lines, err = sound
    assert code == 3, err
    ref = next(x for x in lines if x.get("phase") == "reference" and not x.get("begin"))
    assert ref["ok"] and ref["first_ok"] and ref["decoded"]["ok"], ref
    # three probes, 16 tokens each (the toy's median prompt is under one
    # prefill chunk, so there is no fourth)
    assert ref["decoded"]["positions"] == 3 * 15
    # and a sample of the greedy requests the window itself finished
    win = ref["window"]
    assert win["ok"] and win["requests"] >= 1 and win["positions"] >= 4, win
    assert win["positions"] == sum(n for _, n in win["sampled"])
    assert lines[-1]["rehearsal"] and lines[-1]["would_print"]["correct"] is True


def test_every_phase_says_when_it_begins_and_where_the_run_stands(sound):
    _, lines, _ = sound
    phases = [x for x in lines if "phase" in x]
    assert all(isinstance(x["t_s"], float) for x in phases)
    assert [x["t_s"] for x in phases] == sorted(x["t_s"] for x in phases)
    begun = [x["phase"] for x in phases if x.get("begin")]
    assert begun == ["load", "probes", "warmup_sweep", "warmup_settle", "window",
                     "reference", "control", "shutdown"]
    ended = [x["phase"] for x in phases if not x.get("begin")]
    assert ended == begun                            # each begin line precedes its phase line
    for name in begun:
        b, e = (i for i, x in enumerate(phases) if x["phase"] == name)
        assert phases[b].get("begin") and e > b
    last = phases[-1]
    assert last["phase"] == "shutdown" and last["run_s"] >= last["t_s"] - 0.01
    # the reference runs once the window has closed: its seconds are not set-up
    window = next(x for x in phases if x["phase"] == "window" and not x.get("begin"))
    ref_begin = next(x for x in phases if x["phase"] == "reference" and x.get("begin"))
    assert window["setup_s"] < ref_begin["t_s"]
    # ... and where the window started (also a test of its own, below: tier 1
    # imports this module's tests by name)
    the_run_says_where_its_window_started(lines)


def the_run_says_where_its_window_started(lines):
    settle = next(x for x in lines if x.get("phase") == "warmup_settle" and not x.get("begin"))
    # the toy's count is 6 and it has no ``min_settle_s``: whichever of the
    # count and ``quiet_s`` came last is named
    assert settle["closed_by"] in ("count", "quiet") and settle["sends"] >= 6
    assert (settle["closed_by"] == "count") == (settle["sends"] == 6)
    assert 0 <= settle["sends_in_min_settle"] <= settle["sends"]
    line = lines[-1]["would_print"]
    assert line["settle_sends"] == settle["sends"]
    assert line["settle_closed_by"] == settle["closed_by"]
    # what was compared stays the line's last key
    assert list(line)[-1] == "compared"
    # the run's scratch directory, one a process, went with it
    load = next(x for x in lines if x.get("phase") == "load" and not x.get("begin"))
    assert Path(load["scratch"]).name.startswith("run-") and not Path(load["scratch"]).exists()


def test_the_result_line_says_where_the_window_started_and_what_closed_the_settle(sound):
    the_run_says_where_its_window_started(sound[1])


def test_the_control_is_read_beside_the_reference_and_decides_nothing(sound):
    _, lines, _ = sound
    load = next(x for x in lines if x.get("phase") == "load" and not x.get("begin"))
    assert load["max_slots"] == 3                     # --env reached the engine
    ref = next(x for x in lines if x.get("phase") == "reference" and not x.get("begin"))
    ctl = next(x for x in lines if x.get("phase") == "control" and not x.get("begin"))
    assert ctl["lower"] == "fp8" and ctl["decoded"]["positions"] == ref["decoded"]["positions"]
    assert ctl["window"]["positions"] == ref["window"]["positions"]
    # fp8 activations are further from the reference than the served path is
    assert ctl["decoded"]["median_abs_diff"] > 3 * ref["decoded"]["median_abs_diff"]
    assert lines[-1]["would_print"]["correct"] is True


def test_what_was_compared_ends_standard_error(sound):
    _, _, err = sound
    tail = err.strip().splitlines()[-8:]
    assert all(line.startswith("reference ") for line in tail), tail
    assert "decoded.gap_max" in tail[4] and tail[4].endswith(": ok")
    assert "window.gap_max" in tail[5] and "window.gap_mean" in tail[6]


def test_a_family_the_first_builder_refused_runs_as_files_only():
    """q/k/v biases: a configuration, a reference and two manifest entries
    under ``rehearsal/``, no edit to ``run.py`` or ``lib/``."""
    code, lines, err = rehearse("tinybias.toy_closed")
    assert code == 3, err
    load = next(x for x in lines if x.get("phase") == "load" and not x.get("begin"))
    ref = next(x for x in lines if x.get("phase") == "reference" and not x.get("begin"))
    assert load["weight_bytes"] > 0
    assert ref["ok"] and ref["first_ok"] and ref["decoded"]["ok"], ref
    assert lines[-1]["would_print"]["correct"] is True


@pytest.mark.parametrize("where", ["every program", "the burst program alone"])
def test_a_broken_timed_path_comes_out_not_correct(where):
    code, lines, err = rehearse("tiny.toy_closed", before={
        "every program": BREAK_THE_SAMPLER, "the burst program alone": BREAK_THE_BURST}[where])
    assert code == 3, err
    ref = next(x for x in lines if x.get("phase") == "reference" and not x.get("begin"))
    # the served distributions are the model's; the served tokens are not
    # their argmax, and the widest gap says so
    win = ref["window"]
    assert not win["ok"] and win["gap_max"] > win["gap_tolerance"], win
    if where == "every program":
        assert not ref["ok"] and ref["decoded"]["gap_max"] > ref["decoded"]["gap_tolerance"], ref
    else:
        # the probes never ran the broken program: only the window's own
        # requests can say that it is broken
        assert ref["ok"] and ref["decoded"]["ok"], ref
    assert lines[-1]["would_print"]["correct"] is False
    assert any("FAILS" in line for line in err.splitlines()[-10:])
