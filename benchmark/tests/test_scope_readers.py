"""The table of ``benchmark/lib/scopes.py`` and its nine readers on the small
trace recorded on the chip (``fixtures/trace_scopes.json``: what it holds is
in its ``about``), on a line without a trace, and on a program without the
scope vocabulary."""

import json

import pytest

from benchmark import run
from benchmark.lib import scopes
from benchmark.tests.test_reduce_trace import FIX

MS = 1e6
READERS = ("decode_seq_ms_per_step", "decode_ffn_ms_per_step", "decode_head_ms_per_step",
           "decode_mix_ms_per_step", "decode_glue_ms_per_step", "prefill_seq_ms_per_launch",
           "prefill_ffn_ms_per_launch", "prefill_glue_ms_per_launch", "prefill_device_share")
CELLS = ("granite8b.chat_closed", "granite8b.doc_closed", "xing29b.answer_closed",
         "granite4hmicro.chat32_closed", "lagunaxs2.code_closed")


@pytest.fixture()
def src():
    fx = json.loads((FIX / "trace_scopes.json").read_text())
    return {"planes": {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
                       for p, lines in fx["planes"].items()},
            "op_names": fx["op_names"], "spans": [tuple(s) for s in fx["spans"]],
            "window": tuple(fx["window"]), "span": tuple(fx["span"])}


def reader(name):
    return run.load_module(FIX.parent / "layer_metrics" / f"{name}.py")


def test_the_table_counts_whole_launches_and_names_every_operation(src):
    t = scopes.table(src)
    kinds = {p: (l["kind"], l["n"]) for p, l in t["launches"].items() if l["kind"] != "other"}
    # three decode launches are in the file: the first is cut by the span's
    # start (its module event holds what was seen of it), the last is the
    # plane's last, which the span's end may have cut: ONE is counted
    assert kinds == {"decode_pos_pallas": ("decode", 1), "prefill_chunk_group": ("prefill", 2),
                     "select_end": ("prefill", 2), "finish_admit_group_paged": ("prefill", 1)}
    mods = src["planes"]["/device:TPU:0"]["XLA Modules"]
    assert sum(n.startswith("jit_decode_pos_pallas") for n, _, _ in mods) == 3
    # an operation is counted once, under its instruction's scope or as glue;
    # the while loops that wrap the layers are in the file and in no row
    ops = src["planes"]["/device:TPU:0"]["XLA Ops"]
    assert any(n.endswith(" while") for n, _, _ in ops)
    assert {s for _, s in t["ops"]} >= {"seq/attn", "ffn/mlp", "ffn", "embed", "head/logits",
                                        "head/sample", None}
    assert not any(label.endswith((" while", " conditional", " call")) for _, label in t["glue"])
    # the relayout of wq on entry to every decode launch is glue, by name
    ns, n = t["glue"]["decode_pos_pallas", "%copy.18 copy"]
    assert n == 1 and ns == pytest.approx(2.5625 * MS, rel=1e-3)
    assert t["busy_ns"] <= t["span_ns"]


def test_the_two_sums_agree_with_the_launches(src):
    for kind, launches, forward in (("decode", 1, 1), ("prefill", 5, 2)):
        split = scopes.kind_split(src, kind)
        assert (split["launches"], split["forward"]) == (launches, forward)
        assert sum(split["by_top"].values()) == pytest.approx(split["ns"], rel=scopes.SUMS_APART)
    # the five decode readers sum to the launch's time a step of its 8 (the
    # dispatch spans inside the traced span say 8; the one outside it, 4)
    decode = [reader(n).read(src) for n in READERS[:5]]
    assert decode[3] is None                       # no residual streams in this family
    step_ms = scopes.kind_split(src, "decode")["ns"] / MS / 8
    assert sum(v for v in decode if v) == pytest.approx(step_ms, rel=scopes.SUMS_APART)
    assert decode[4] == pytest.approx(0.4830, rel=1e-3) and decode[1] > decode[0] > decode[2] > 0
    # the three prefill readers sum to the prefill launches' time over the two
    # that run the model: the finish and the select_ends are shared out as
    # glue, and a finish is no launch of the model for holding its sampling
    assert ("finish_admit_group_paged", "head/sample") in scopes.table(src)["ops"]
    prefill = [reader(n).read(src) for n in READERS[5:8]]
    total_ms = scopes.kind_split(src, "prefill")["ns"] / MS / 2
    assert sum(prefill) == pytest.approx(total_ms, rel=scopes.SUMS_APART)
    assert prefill == [pytest.approx(v, rel=1e-3) for v in (0.68322, 0.87801, 3.75273)]
    assert reader("prefill_device_share").read(src) == pytest.approx(70.410, rel=1e-3)


def test_a_reader_gives_nothing_where_the_sums_part(src):
    """Operations counted twice (a container taken for an operation, a name
    that two lines share) or time no operation covers: beyond 2 % the split
    is not a reading."""
    ops = src["planes"]["/device:TPU:0"]["XLA Ops"]
    big = next(e for e in ops if e[0] == "%copy.18 copy" and e[2] > 2 * MS)
    src["planes"] = {"/device:TPU:0": dict(src["planes"]["/device:TPU:0"],
                                           **{"XLA Ops": ops + [("%copy.99 copy", big[1], big[2])]})}
    assert scopes.kind_split(src, "decode") is None
    assert reader("decode_glue_ms_per_step").read(src) is None
    assert reader("prefill_seq_ms_per_launch").read(src) is not None   # the other kind still reads


def test_nothing_to_read_is_none_and_never_raises(src, monkeypatch):
    empty = {"planes": {}, "window": (0.0, 1.0), "spans": []}
    no_steps = dict(src, spans=[])                 # no decode dispatch in the traced span
    no_names = dict(src, op_names={})              # a program whose operations carry no scope
    for name in READERS:
        assert reader(name).read(empty) is None
        assert reader(name).read(no_names) is None
        if name.startswith("decode_"):
            assert reader(name).read(no_steps) is None
    # a parent commit: the program has no kinds table and no vocabulary
    monkeypatch.setattr(scopes, "_vocabulary", lambda: None)
    parent = dict(src, planes=dict(src["planes"]))   # another run's planes: read anew
    assert all(reader(name).read(parent) is None for name in READERS)


def test_the_command_prints_the_table_of_a_trace_file(src, tmp_path, capsys, monkeypatch):
    monkeypatch.setattr(scopes.reduce_trace, "load_planes", lambda path: src["planes"])
    monkeypatch.setattr(scopes, "metadata_op_names", lambda path: src["op_names"])
    assert scopes.main([str(tmp_path / "t.xplane.pb")]) == 0
    out = capsys.readouterr().out
    assert "decode_pos_pallas" in out and "seq/attn" in out and "(glue)" in out
    assert "%copy.18 copy" in out.split("glue by operation")[1]
    # no batcher ran in a process that reads a capture: the kinds come from
    # the names alone
    assert "decode_pos_pallas                  decode" in out
    assert scopes.main([]) == 2


def test_the_wire_reader_finds_a_stat_of_an_events_metadata(tmp_path):
    """``metadata_op_names`` on a hand-made XSpace: a device plane whose event
    metadata carries ``tf_op`` as a string and, for another event, as a
    reference to a stat's name; a host plane and the lines are stepped over."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    def field(num, payload):  # length-delimited
        return varint(num << 3 | 2) + varint(len(payload)) + payload

    def number(num, value):
        return varint(num << 3) + varint(value)

    def entry(key, message):  # a map<int64, message> entry
        return number(1, key) + field(2, message)

    stat_md = [field(5, entry(i, number(1, i) + field(2, name.encode())))
               for i, name in ((7, "tf_op"), (8, "program_id"), (9, "jit(f)/seq/attn/add:"))]
    fusion = (number(1, 1) + field(2, b"%fusion.5 = bf16[8] fusion(...)")
              + field(5, number(1, 8) + number(3, 4242))
              + field(5, number(1, 7) + field(5, b"jit(f)/while/body/ffn/mlp/dot_general:")))
    add = (number(1, 2) + field(2, b"%add.1 = f32[] add(...)")
           + field(5, number(1, 8) + number(3, 4242)) + field(5, number(1, 7) + number(7, 9)))
    copy = number(1, 3) + field(2, b"%copy.1 = s8[4] copy(...)") + field(
        5, number(1, 8) + number(3, 4242))          # compiler-made: no tf_op
    line = field(3, number(1, 1) + field(2, b"XLA Ops") + field(4, number(1, 1) + number(2, 5)))
    device = (number(1, 1) + field(2, b"/device:TPU:0") + line
              + b"".join(field(4, entry(i + 1, m)) for i, m in enumerate((fusion, add, copy)))
              + b"".join(stat_md))
    host = number(1, 2) + field(2, b"/host:CPU") + field(4, entry(1, fusion)) + b"".join(stat_md)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, host) + field(1, device))
    assert scopes.metadata_op_names(str(path)) == {"4242": {
        "%fusion.5 = bf16[8] fusion(...)": "jit(f)/while/body/ffn/mlp/dot_general:",
        "%add.1 = f32[] add(...)": "jit(f)/seq/attn/add:"}}


@pytest.mark.parametrize("name", READERS)
def test_every_reader_is_in_the_manifest_with_its_own_list_of_cells(name):
    man = json.loads((FIX.parent.parent / "BENCHMARK.json").read_text())
    listed = next(m for m in man["per_layer"] if m["name"] == name)
    cells = ["xing29b.answer_closed"] if name == "decode_mix_ms_per_step" else list(CELLS)
    assert listed == dict(reader(name).METRIC, workloads=cells)
