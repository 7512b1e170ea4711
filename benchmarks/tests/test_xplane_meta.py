"""`lib.xplane_meta` on an XSpace written by hand in the wire format:
the per-kind attributes come out under the event's name, string values
and references alike, and `lib.trace_lines` sums the device time of
the operations under a scope inside a program's runs."""

import pytest

from benchmarks.lib import trace_lines, xplane_meta


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def stat(metadata_id: int, text=None, ref=None) -> bytes:
    body = field(1, metadata_id)
    body += field(5, text) if text is not None else field(7, ref)
    return body


def plane(name: str, kinds: dict, stat_names: dict) -> bytes:
    body = field(1, 7) + field(2, name)
    for key, (event_name, stats) in kinds.items():
        meta = field(1, key) + field(2, event_name) + b"".join(
            field(5, s) for s in stats)
        body += field(4, field(1, key) + field(2, meta))
    for key, stat_name in stat_names.items():
        body += field(5, field(1, key) + field(2, field(1, key) +
                                               field(2, stat_name)))
    return body


OP = "%fusion.7 = bf16[64,640]{1,0} fusion(...), kind=kLoop"
DOT = "%ragged-dot-none.1 = bf16[512,768]{1,0} custom-call(...)"
SPACE = field(1, plane("/device:TPU:0", {
    1: (OP, [stat(10, text="jit(decode_fn)/model/fstpu_mla_decode_"
                             "attention/take"), stat(11, ref=12)]),
    2: (DOT, [stat(10, ref=13)]),
    3: ("%copy.1 = bf16[8]{0} copy(...)", [])},
    {10: "tf_op", 11: "hlo_category", 12: "data formatting",
     13: "jit(decode_fn)/model/layers_1/mlp/fstpu_moe_experts/ragged_dot"})
) + field(1, plane("/host:CPU", {1: ("serving/decode", [])}, {}))


def test_per_kind_attributes_come_out_under_the_events_name(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(SPACE)
    got = xplane_meta.load(str(path))
    assert set(got) == {"/device:TPU:0", "/host:CPU"}
    device = got["/device:TPU:0"]
    assert device[OP] == ("tf_op=jit(decode_fn)/model/fstpu_mla_decode_"
                          "attention/take hlo_category=data formatting")
    assert "fstpu_moe_experts" in device[DOT]
    assert len(device) == 2 and got["/host:CPU"] == {}
    only = xplane_meta.load(str(path), lambda name: name.startswith("/host"))
    assert set(only) == {"/host:CPU"}


def test_a_varint_longer_than_a_byte_and_unknown_fields_are_walked():
    message = field(3, 300) + field(9, "x" * 200) + field(2, "name")
    got = [(n, k, bytes(v) if k == 2 else v)
           for n, k, v in xplane_meta.fields(memoryview(message))]
    assert got == [(3, 0, 300), (9, 2, b"x" * 200), (2, 2, b"name")]


def test_seconds_under_a_scope_inside_each_run_of_the_program():
    # two ticks; the scope's operations are a while (1.0-1.4) holding
    # two of its body's (1.1-1.2, 1.25-1.35), and one in the second
    # tick; a third run is cut by the window's end
    obs = {"trace": {"devices": {"/device:TPU:0": [["op", 1.0, 0.1]]},
                     "host": []},
           "trace_window": (0.0, 3.0),
           "trace_attrs": {"spans": [], "modules": [
               ["jit_decode_fn", 0.9, 0.6], ["jit_decode_fn", 2.0, 0.5],
               ["jit_decode_fn", 2.8, 0.5]]},
           "scope_ops": [["%while.1 ... fstpu_moe_experts/while", 1.0, 0.4],
                         ["%a ... fstpu_moe_experts/ragged_dot", 1.1, 0.1],
                         ["%b ... fstpu_moe_experts/gather", 1.25, 0.1],
                         ["%c ... fstpu_moe_route/top_k", 1.45, 0.02],
                         ["%d ... fstpu_moe_experts/ragged_dot", 2.1, 0.2],
                         ["%e ... fstpu_moe_experts/ragged_dot", 2.9, 0.2]]}
    got = trace_lines.scope_seconds_in(obs, "fstpu_moe_experts",
                                       trace_lines.DECODE)
    assert got[1] == 2 and abs(got[0] - (0.4 + 0.2)) < 1e-9
    assert trace_lines.seconds_a_run(got) == pytest.approx(0.3)
    assert trace_lines.scope_seconds_in(obs, "fstpu_absent",
                                        trace_lines.DECODE) is None
    assert trace_lines.scope_seconds_in({"trace": None}, "x",
                                        trace_lines.DECODE) is None
    assert trace_lines.seconds_a_run(None) is None
