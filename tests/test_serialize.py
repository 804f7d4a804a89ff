"""Canonical serialization codec tests."""
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import pytest

from tpubft.utils.serialize import (SerializeError, decode_msg, encode_msg,
                                    read_uvarint, write_uvarint)


@dataclass
class Inner:
    SPEC = [("a", "u32"), ("b", "bytes")]
    a: int
    b: bytes


@dataclass
class Outer:
    SPEC = [
        ("x", "u64"),
        ("flag", "bool"),
        ("name", "str"),
        ("items", ("list", "u16")),
        ("digest", ("fixed", "u8", 4)),
        ("table", ("map", "str", "u32")),
        ("maybe", ("opt", "bytes")),
        ("inner", ("msg", Inner)),
    ]
    x: int
    flag: bool
    name: str
    items: List[int]
    digest: List[int]
    table: Dict[str, int]
    maybe: Optional[bytes]
    inner: Inner


def make():
    return Outer(x=2**63, flag=True, name="héllo", items=[1, 65535],
                 digest=[1, 2, 3, 4], table={"b": 2, "a": 1},
                 maybe=None, inner=Inner(a=7, b=b"\x00\xff"))


def test_roundtrip():
    m = make()
    assert decode_msg(encode_msg(m), Outer) == m


def test_canonical_map_order():
    m1 = make()
    m2 = make()
    m2.table = {"a": 1, "b": 2}  # different insertion order
    assert encode_msg(m1) == encode_msg(m2)


def test_optional_present():
    m = make()
    m.maybe = b"xyz"
    assert decode_msg(encode_msg(m), Outer).maybe == b"xyz"


def test_trailing_bytes_rejected():
    with pytest.raises(SerializeError):
        decode_msg(encode_msg(make()) + b"\x00", Outer)


def test_truncation_rejected():
    data = encode_msg(make())
    with pytest.raises(SerializeError):
        decode_msg(data[:-1], Outer)


def test_uvarint_roundtrip():
    for v in [0, 1, 127, 128, 300, 2**32, 2**60]:
        buf = bytearray()
        write_uvarint(buf, v)
        out, off = read_uvarint(memoryview(bytes(buf)), 0)
        assert (out, off) == (v, len(buf))


def test_fixed_length_enforced():
    m = make()
    m.digest = [1, 2, 3]
    with pytest.raises(SerializeError):
        encode_msg(m)


def test_config():
    from tpubft.utils.config import ReplicaConfig
    c = ReplicaConfig(f_val=1, c_val=0)
    assert c.n_val == 4 and c.slow_path_quorum == 3 and c.optimistic_fast_quorum == 4
    c2 = ReplicaConfig.from_json(c.to_json())
    assert c2 == c
    c3 = ReplicaConfig(f_val=2, c_val=1)
    assert c3.n_val == 9 and c3.fast_path_threshold_quorum == 8


def test_config_declares_each_field_once_and_something_reads_it():
    """A dataclass takes a second declaration of a name in silence (the
    last default wins), and a field nothing reads is an option that
    selects nothing: both were in the tree once."""
    import ast
    import dataclasses
    import os
    import re
    from tpubft.utils import config
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tree = ast.parse(open(config.__file__, encoding="utf-8").read())
    cls = next(n for n in tree.body
               if isinstance(n, ast.ClassDef) and n.name == "ReplicaConfig")
    declared = [n.target.id for n in cls.body
                if isinstance(n, ast.AnnAssign)]
    assert sorted(declared) == sorted(set(declared))
    assert declared == [f.name for f in
                        dataclasses.fields(config.ReplicaConfig)]
    sources = []
    for top in ("tpubft", "cellbench", "tools", "benchmarks"):
        for d, _dirs, files in os.walk(os.path.join(root, top)):
            sources += [os.path.join(d, f) for f in files
                        if f.endswith(".py")]
    sources.append(os.path.join(root, "chip_smoke.py"))
    text = "\n".join(open(f, encoding="utf-8").read() for f in sources
                     if os.path.abspath(f) != os.path.abspath(
                         config.__file__))
    words = set(re.findall(r"[A-Za-z_][A-Za-z_0-9]*", text))
    assert [n for n in declared if n not in words] == []


def test_config_overrides_are_coerced_and_unknown_names_refused():
    from tpubft.utils.config import parse_config_overrides
    assert parse_config_overrides(
        ["execution_max_accumulation=1", "optimistic_replies=true",
         "threshold_scheme=threshold-bls", "autotune_enabled=0"]) == {
        "execution_max_accumulation": 1, "optimistic_replies": True,
        "threshold_scheme": "threshold-bls", "autotune_enabled": False}
    assert parse_config_overrides(None) == {}
    for bad in ("no_such_field=False",
                "f_val=2",                    # topology: its own flag
                "view_change_timer_ms"):      # no value
        with pytest.raises(SystemExit):
            parse_config_overrides([bad])
    with pytest.raises(ValueError):
        parse_config_overrides(["concurrency_level=many"])


def test_i64_range_checked():
    from dataclasses import dataclass

    @dataclass
    class M:
        SPEC = [("v", "i64")]
        v: int

    assert decode_msg(encode_msg(M(v=-5)), M).v == -5
    assert decode_msg(encode_msg(M(v=2**63 - 1)), M).v == 2**63 - 1
    with pytest.raises(SerializeError):
        encode_msg(M(v=2**63))
    with pytest.raises(SerializeError):
        encode_msg(M(v=-(2**63) - 1))


def test_uvarint_rejects_overlong():
    with pytest.raises(SerializeError):
        read_uvarint(memoryview(b"\x80\x00"), 0)  # non-minimal zero
    with pytest.raises(SerializeError):
        read_uvarint(memoryview(b"\xff" * 9 + b"\x7f"), 0)  # > 64 bits
    # canonical max u64 still decodes
    buf = bytearray()
    write_uvarint(buf, 2**64 - 1)
    assert read_uvarint(memoryview(bytes(buf)), 0)[0] == 2**64 - 1
