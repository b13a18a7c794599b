"""The comparison that decides the reference part of ``correct``: the served
path's top-k log-probabilities against the plain reference's, at the first
generated token (out of a prefill program) and at every token decoded after
it through the cache; and the token ids of a sample of the requests the
window itself finished, against the reference's best at each position."""

from __future__ import annotations

import statistics

import numpy as np

TOP_K = 5
PROBES = 3
PROBE_TOKENS = 64
# Every probe asks for this many tokens at temperature 0 with logprobs: the
# first comes out of the prefill, the others out of decode steps that read
# the KV pool through the slot's block table, write the new row at the
# slot's position and, at KV_BLOCK_TOKENS 16 after a 64-token prompt, open a
# new pool block. The reference runs ONE float32 forward over the prompt and
# the served tokens (teacher-forced: a served argmax that bf16 noise flipped
# does not derail the positions after it).
DECODE_TOKENS = 16

# THE FIRST TOKEN. For each probe, every token of the served top-5 is
# compared with the reference's log-probability of the same token (the
# reference has the whole vocabulary). The check passes when the median of
# all those differences is within MEDIAN_TOL, each is within TOKEN_TOL, and
# each prompt's reference argmax is in its served top-5.
#
# Both sides read the same int8 codes and scales, so quantisation is not in
# the difference. What is: bf16 activations (8 mantissa bits) against
# float32. Every matmul, norm and residual addition of the served path
# rounds to bf16, a relative error of 2^-9 each; through 40 layers (~400
# roundings, adding like a random walk: ~8 % of the stream) and a peaked
# attention (weights.QK_GAIN) that amplifies a perturbation from layer to
# layer, the logits, whose spread is 10 (weights.ASCII_LOGIT_STD), come out
# with noise of about 1, and a log-probability below the top one is the
# difference of two of them. Measured on the chip (PR 23, calls 2-4, one
# number per run): largest single difference 0.58 to 3.26.
#
# A dropped Granite multiplier, a wrong kv-head grouping, a wrong mask or a
# wrong block table changes the logits by their own spread: differences of
# ~14 (10 x sqrt 2) on every token, and the argmax leaves the top-5
# (benchmark/tests/test_reference.py breaks each on purpose). The tolerance
# is NOT tight enough to tell bf16 activations from fp8 ones; a later
# benchmark PR that wants that needs a trained checkpoint's smoother logits
# or many more probes.
MEDIAN_TOL = 2.0
TOKEN_TOL = 6.0
MIN_SHARED = 3

# THE DECODED POSITIONS are compared the same way, pooled over all probes,
# and reported apart. Three numbers decide:
#   the median of the differences      the noise is the first token's (the
#                                      same layers over the same KV, read
#                                      from the pool instead of the prefill's
#                                      own rows)
#   the largest single difference      over ~20x as many numbers as the first
#                                      token's 15, so the same noise reaches
#                                      further out: the limit stands higher
#   the widest gap                     how far the SERVED token's reference
#                                      log-probability lies below the
#                                      reference's best. A greedy token is
#                                      the served argmax, so the gap is 0
#                                      wherever both sides agree on it, and
#                                      under noise it is at most the distance
#                                      between two near-tied candidates
# A wrong position, a wrong block-table entry or a stale KV block puts the
# decode step on other keys and values than the reference's: from that
# position on the logits move by their own spread (differences of ~14, a
# served token picked from another distribution: gap ~10-30). The tests in
# benchmark/tests/test_decode_check.py break each on purpose. Whether the
# reference's argmax is in the served top-5, and how many of the two top-5
# agree, is REPORTED for the decoded positions (``argmax_misses``,
# ``shared_min``) and does not decide: over 60 positions a run, the fifth and
# sixth candidates change places under noise alone, and the numbers above
# already fail every fault that rule fails.
# Measured on the chip (PR 28; PERF.md section 2 has every reading). The
# served path, 39 distinct checks over 30 seeds and both cells: median
# 0.466-0.64, largest single difference 2.58-4.30 (99th percentile 2.4-3.3),
# widest gap 0.54-3.04. THE CONTROL, the plain reference with fp8 (e4m3)
# activations and KV put in the served path's place at the same prompts and
# tokens (``--control fp8``; the step below the bf16 the configuration serves
# in), 14 runs over 14 seeds: median 2.82-4.33, largest 16.5-23.8, widest gap
# 9.49-21.4. Each limit stands between the two with room on both sides: 2.0x
# / 1.9x / 2.0x the served path's largest, 2.2x / 2.1x / 1.6x under the
# control's smallest. The first position's numbers do NOT separate the two
# (the control reads a median of 2.0-4.9 where the served path has read up to
# 1.11): 15-20 numbers a run are too few, and the limits there are PR 23's.
DECODED_MEDIAN_TOL = 1.3
DECODED_TOKEN_TOL = 8.0
GAP_TOL = 6.0

# THE WINDOW'S OWN REQUESTS. The probes above are served in set-up, alone, by
# the one-step ``_ext`` decode program (a request with logprobs runs it). The
# window times the BURST decode program at the mix's own load, and a reply
# without logprobs carries token ids only. So a mix sends every k-th request
# greedy (``greedy_every``), and of those the window finished a sample drawn
# from the seed, the longest among them, is held to the reference by its ids:
# one float32 forward over the prompt and the served tokens, and at each
# served position the gap by which the served token's reference
# log-probability lies below the reference's best. Two numbers decide: the
# widest gap and, steadier from seed to seed, the mean gap over all positions.
# Measured on the chip (PR 28, 192-431 positions a run, 17 checks): the
# served path reads a widest gap of 1.33-2.58 and a mean of 0.035-0.080 (6-11 %
# of the positions are not the reference's argmax: near-ties that bf16 noise
# decides); the fp8 control 11.8-18.9 and 1.62-2.39 (39-52 % of the
# positions). The limits: 2.3x the served path's largest and 2.0x under the
# control's smallest; 4.4x and 4.6x. The program's own int8 KV (``--env
# TPU_KV_QUANT=int8``, one run) reads 3.65 and 0.079, and 0.64 / 3.46 / 1.14
# on the decoded numbers above: inside the bf16 readings or beside them, so
# NO number here tells int8 KV with a scale a row from bf16 KV.
WINDOW_SAMPLE_TOKENS = 300
WINDOW_SAMPLE_MAX = 6
WINDOW_GAP_TOL = 6.0
WINDOW_GAP_MEAN_TOL = 0.35
# WHAT THIS CANNOT SEE: the sampled (temperature 0.8) requests' tokens, only
# the greedy ones' beside them in the same bursts. int8 KV from bf16 KV
# (above). And two full blocks inside a slot's frontier may change places in
# its table unseen: a key carries its rotary position, and softmax attention
# does not care in which order it meets the keys.


def _token_bytes(entry: dict) -> list[int]:
    """The bytes of a reply entry's token; under the byte-level tokenizer a
    printable token is one byte and its id is that byte."""
    return entry.get("bytes") or list(entry["token"].encode())


def served_top(entries: list[dict]) -> dict[int, float]:
    """Reply ``top_logprobs`` entries -> {token id: logprob}."""
    return {int(_token_bytes(e)[0]): float(e["logprob"])
            for e in entries if len(_token_bytes(e)) == 1}


def served_tokens(entries: list[dict]) -> list[int]:
    """The token id of each ``logprobs.content`` entry of a reply."""
    for e in entries:
        if len(_token_bytes(e)) != 1:
            raise ValueError(f"served token {e.get('token')!r} is not one byte: "
                             "the byte-level tokenizer cannot name its id")
    return [int(_token_bytes(e)[0]) for e in entries]


def compare(ref_logprobs: np.ndarray, served_entries: list[dict]) -> dict:
    """One position: differences on the served top-5, and whether the two
    top-5 lists overlap as they must."""
    served = served_top(served_entries)
    order = [int(i) for i in np.argsort(-ref_logprobs)[:TOP_K]]
    diffs = [abs(float(ref_logprobs[t]) - lp) for t, lp in served.items()]
    shared = len(set(order) & set(served))
    return {"diffs": diffs, "shared": shared,
            "argmax_in_served": order[0] in served and shared >= MIN_SHARED}


def compare_all(pairs: list[tuple[np.ndarray, list[dict]]],
                median_tol: float = MEDIAN_TOL, token_tol: float = TOKEN_TOL) -> dict:
    """One position of each probe: ``ok``, the median and the largest
    difference."""
    per = [compare(ref, served) for ref, served in pairs]
    diffs = [d for p in per for d in p["diffs"]]
    median = statistics.median(diffs) if diffs else float("inf")
    worst = max(diffs) if diffs else float("inf")
    argmax_ok = all(p["argmax_in_served"] for p in per) and len(diffs) >= MIN_SHARED * len(per)
    ok = argmax_ok and median <= median_tol and worst <= token_tol
    return {"ok": bool(ok), "median_abs_diff": median, "max_abs_diff": worst,
            "shared": [p["shared"] for p in per], "argmax_ok": bool(argmax_ok), "n": len(diffs),
            "median_tolerance": median_tol, "token_tolerance": token_tol}


def compare_decoded(probes: list[tuple[np.ndarray, list[dict]]],
                    median_tol: float = DECODED_MEDIAN_TOL,
                    token_tol: float = DECODED_TOKEN_TOL,
                    gap_tol: float = GAP_TOL) -> dict:
    """Positions 1.. of each probe, pooled. A probe is (reference
    log-probabilities [n, vocab], the reply's n ``logprobs.content``
    entries)."""
    diffs: list[float] = []
    gaps: list[float] = []
    shared_min, misses, positions = [], 0, 0
    for ref, entries in probes:
        per = [compare(ref[i], entries[i]["top_logprobs"]) for i in range(1, len(entries))]
        toks = served_tokens(entries)
        gaps += [float(ref[i].max() - ref[i][toks[i]]) for i in range(1, len(entries))]
        diffs += [d for p in per for d in p["diffs"]]
        shared_min.append(min((p["shared"] for p in per), default=0))
        misses += sum(not p["argmax_in_served"] for p in per)
        positions += len(per)
    median = statistics.median(diffs) if diffs else float("inf")
    worst = max(diffs, default=float("inf"))
    gap = max(gaps, default=float("inf"))
    ok = (positions > 0 and len(diffs) >= MIN_SHARED * positions
          and median <= median_tol and worst <= token_tol and gap <= gap_tol)
    return {"ok": bool(ok), "median_abs_diff": median, "max_abs_diff": worst,
            "p99_abs_diff": float(np.quantile(diffs, 0.99)) if diffs else None,
            "gap_max": gap, "shared_min": shared_min, "argmax_misses": misses,
            "positions": positions, "n": len(diffs), "median_tolerance": median_tol,
            "token_tolerance": token_tol, "gap_tolerance": gap_tol}


def compare_probes(probes: list[tuple[np.ndarray, list[dict]]], first_tol: dict | None = None,
                   decoded_tol: dict | None = None) -> dict:
    """The whole reference check: the first position as ``compare_all`` has
    always reported it, the decoded positions under ``decoded``, and ``ok``
    for both together."""
    for ref, entries in probes:
        if len(ref) != len(entries) or not entries:
            raise ValueError(f"reference rows {len(ref)} for {len(entries)} served tokens")
    first = compare_all([(ref[0], e[0]["top_logprobs"]) for ref, e in probes], **(first_tol or {}))
    decoded = compare_decoded(probes, **(decoded_tol or {}))
    return dict(first, ok=first["ok"] and decoded["ok"], first_ok=first["ok"], decoded=decoded)


def window_sample(records: list, w0: float, w1: float, seed: int) -> list:
    """Which of the window's requests are held to the reference: of the
    greedy ones that finished inside [w0, w1), the longest, then others drawn
    from the seed until WINDOW_SAMPLE_TOKENS served tokens or
    WINDOW_SAMPLE_MAX requests are reached."""
    import random

    # a reply of one-byte tokens only: its bytes are its token ids
    done = [r for r in records if r.ok and r.temperature == 0.0 and not r.logprobs
            and r.t_done is not None and w0 <= r.t_done < w1
            and len(r.text.encode()) == r.max_tokens]
    done.sort(key=lambda r: (-(r.prompt_tokens + r.max_tokens), r.idx))
    rest = done[1:]
    random.Random(seed ^ 0x5A3B1E).shuffle(rest)
    out: list = []
    for r in done[:1] + rest:
        if len(out) >= WINDOW_SAMPLE_MAX or sum(x.max_tokens for x in out) >= WINDOW_SAMPLE_TOKENS:
            break
        out.append(r)
    return out


def token_gaps(ref_logprobs: np.ndarray, tokens: list[int]) -> list[float]:
    """How far each token's reference log-probability lies below the
    reference's best at its position (0 where the token IS the best)."""
    return [float(ref_logprobs[i].max() - ref_logprobs[i][tok]) for i, tok in enumerate(tokens)]


def compare_window(samples: list[tuple[np.ndarray, list[int]]],
                   gap_tol: float = WINDOW_GAP_TOL, mean_tol: float = WINDOW_GAP_MEAN_TOL) -> dict:
    """The requests the window itself finished: (reference log-probabilities
    [n, vocab], the n served token ids) each. Only ids are needed, so these
    are tokens of the burst decode program the window times, at its own load."""
    gaps = [g for ref, toks in samples for g in token_gaps(ref, toks)]
    widest = max(gaps, default=float("inf"))
    mean = statistics.fmean(gaps) if gaps else float("inf")
    return {"ok": bool(gaps and widest <= gap_tol and mean <= mean_tol),
            "requests": len(samples), "positions": len(gaps), "gap_max": widest,
            "gap_mean": mean, "flipped": sum(g > 0 for g in gaps),
            "gap_tolerance": gap_tol, "gap_mean_tolerance": mean_tol}


def entries_of(logprobs: np.ndarray) -> list[dict]:
    """Rows of log-probabilities [n, vocab] as a reply's ``logprobs.content``
    would hold them, the greedy token served at each: how a forward pass that
    is not the program's (the lower-precision control) is put in the served
    path's place."""
    def one(lp, i):
        return {"token": chr(int(i)), "bytes": [int(i)], "logprob": float(lp[i])}

    return [dict(one(lp, int(np.argmax(lp))),
                 top_logprobs=[one(lp, i) for i in np.argsort(-lp)[:TOP_K]]) for lp in logprobs]


def compared(check: dict, window: dict | None = None) -> list[str]:
    """Each number compared beside its limit, one line each."""
    d = check["decoded"]
    rows = [("first.median_abs_diff", check["median_abs_diff"], check["median_tolerance"]),
            ("first.max_abs_diff", check["max_abs_diff"], check["token_tolerance"]),
            ("decoded.median_abs_diff", d["median_abs_diff"], d["median_tolerance"]),
            ("decoded.max_abs_diff", d["max_abs_diff"], d["token_tolerance"]),
            ("decoded.gap_max", d["gap_max"], d["gap_tolerance"])]
    if window is not None:
        rows += [("window.gap_max", window["gap_max"], window["gap_tolerance"]),
                 ("window.gap_mean", window["gap_mean"], window["gap_mean_tolerance"])]
    return [f"reference {name} {value:.6g} <= {limit:g}: {'ok' if value <= limit else 'FAILS'}"
            for name, value, limit in rows] + [
        f"reference first.shared {check['shared']} >= {MIN_SHARED} each, argmax served: "
        f"{'ok' if check['argmax_ok'] else 'FAILS'}"]
