"""Tier-1 wiring for the dispatcher hot-path lint
(tools/check_hotpath.py): the admitted-message handlers — everything an
AdmittedMsg reaches synchronously on the consensus dispatcher — must
contain no direct `unpack()` / `.verify()` / `.verify_batch()` call
sites. Parse and signature checks belong to the admission plane (or to
the explicitly-named `_verify_*` fallback seams for the
admission_workers=0 path), keeping the control thread lean by
construction."""
import ast
import importlib.util
import os
import textwrap

_TOOL = os.path.join(os.path.dirname(__file__), "..", "tools",
                     "check_hotpath.py")
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _load_tool():
    spec = importlib.util.spec_from_file_location("check_hotpath",
                                                  os.path.abspath(_TOOL))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_hot_path_handlers_are_lean():
    tool = _load_tool()
    violations = tool.find_violations(_ROOT)
    assert violations == [], (
        "parse/verify call sites found in dispatcher hot-path handlers "
        "(route through the admission plane / _verify_* seams):\n"
        + "\n".join(f"{p}:{ln}: {msg}" for p, ln, msg in violations))


def test_lint_catches_a_violation(tmp_path):
    """The lint must actually detect a verify/unpack call inside a listed
    handler (including nested closures), and must flag a handler that
    disappears from the source (a rename silently escaping coverage)."""
    tool = _load_tool()
    # narrow the freshly-loaded tool's list to the one synthetic file
    # (the module is loaded per-test, so this never leaks)
    del tool.HOT_PATH[("tpubft/consensus/replica.py", "Replica")]
    mod_dir = tmp_path / "tpubft" / "consensus"
    mod_dir.mkdir(parents=True)
    (mod_dir / "incoming.py").write_text(textwrap.dedent("""\
        class Dispatcher:
            def _loop_body(self):
                msg = m.unpack(raw)
                def nested():
                    return self.sig.verify(b"x", b"y")
                return nested
    """))
    violations = tool.find_violations(str(tmp_path))
    msgs = [msg for _, _, msg in violations]
    assert any("unpack" in s for s in msgs), violations
    assert any("verify" in s for s in msgs), violations
    # a handler disappearing from the source (rename escaping coverage)
    # is itself a violation
    (mod_dir / "incoming.py").write_text(
        "class Dispatcher:\n    def renamed(self):\n        pass\n")
    violations = tool.find_violations(str(tmp_path))
    assert any("not found" in msg for _, _, msg in violations), violations


def test_lint_catches_telemetry_violations(tmp_path):
    """Seeded defects for the telemetry rule: span allocation
    (get_tracer/start_span/set_tag) and f-string construction inside a
    hot-path handler are flagged — hot-path observability may only ride
    the bounded flight.record() API. A handler that records through
    flight.record (and logs with %-style lazy formatting) stays clean."""
    tool = _load_tool()
    del tool.HOT_PATH[("tpubft/consensus/replica.py", "Replica")]
    mod_dir = tmp_path / "tpubft" / "consensus"
    mod_dir.mkdir(parents=True)
    (mod_dir / "incoming.py").write_text(textwrap.dedent("""\
        class Dispatcher:
            def _loop_body(self):
                with get_tracer().start_span("hot") as span:
                    span.set_tag("msg", f"seq={self.seq}")
    """))
    violations = tool.find_violations(str(tmp_path))
    msgs = [msg for _, _, msg in violations]
    assert any("start_span" in s and "flight.record" in s for s in msgs), \
        violations
    assert any("set_tag" in s for s in msgs), violations
    assert any("f-string" in s for s in msgs), violations
    # the sanctioned shape passes clean
    (mod_dir / "incoming.py").write_text(textwrap.dedent("""\
        class Dispatcher:
            def _loop_body(self):
                flight.record(flight.EV_DISPATCH, seq=self.seq)
                log.debug("handled %d", self.seq)
    """))
    assert tool.find_violations(str(tmp_path)) == []


def test_lint_rejects_flight_span_in_a_hot_handler(tmp_path):
    """`flight.span` is for batch-level work OFF the dispatcher (one
    object, one profiler annotation per interval): inside a hot handler
    it is flagged like `start_span`; `flight.record` stays the one
    sanctioned call, and reading a `.span` attribute is not a call."""
    tool = _load_tool()
    del tool.HOT_PATH[("tpubft/consensus/replica.py", "Replica")]
    mod_dir = tmp_path / "tpubft" / "consensus"
    mod_dir.mkdir(parents=True)
    (mod_dir / "incoming.py").write_text(textwrap.dedent("""\
        class Dispatcher:
            def _loop_body(self):
                with flight.span("handler", seq=self.seq):
                    self.handle()
    """))
    violations = tool.find_violations(str(tmp_path))
    assert len(violations) == 1, violations
    assert "calls span()" in violations[0][2] \
        and "flight.record" in violations[0][2]
    (mod_dir / "incoming.py").write_text(textwrap.dedent("""\
        class Dispatcher:
            def _loop_body(self):
                flight.record(flight.EV_DISPATCH, seq=self.seq)
                if self.info.span is not None:
                    self.info.span = None
    """))
    assert tool.find_violations(str(tmp_path)) == []


def test_hot_path_list_matches_source():
    """Every listed handler exists in the real tree (find_violations
    reports missing ones; an empty result implies full coverage)."""
    tool = _load_tool()
    for (rel, cls), fns in tool.HOT_PATH.items():
        path = os.path.join(_ROOT, rel)
        tree = ast.parse(open(path, "rb").read())
        names = {item.name for node in tree.body
                 if isinstance(node, ast.ClassDef) and node.name == cls
                 for item in node.body
                 if isinstance(item, ast.FunctionDef)}
        assert fns <= names, (rel, cls, fns - names)
