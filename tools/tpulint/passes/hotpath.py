"""Pass: dispatcher hot-path lint (migrated from tools/check_hotpath.py).

The admitted-message handlers — everything an AdmittedMsg reaches
synchronously on the consensus dispatcher — must contain no direct
`unpack()` / `.verify()` / `.verify_batch()` call sites: parse and
signature checks belong to the admission plane (or to the explicitly
named `_verify_*` fallback seams for the admission_workers=0 path).

They must also emit telemetry ONLY through the bounded flight-recorder
API (`flight.record(...)` — tpubft/utils/flight.py): span allocation
(`get_tracer`/`start_span`/`set_tag`, and `flight.span`, which is for
batch-level work OFF the dispatcher) and f-string construction are
per-message heap work the hot path must not pay — the recorder exists
precisely so hot-seam observability costs one tuple into a
preallocated ring. (Logging through %-style lazy formatting stays
allowed: it only formats when the level is live.)

A handler disappearing from the source is itself a violation — the
list must track the code. tools/check_hotpath.py remains the CLI shim.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, List, Set, Tuple

from tools.tpulint.core import Finding

PASS_ID = "hotpath"

# (module path, class name) -> function names forming the dispatcher's
# admitted-message hot path: the loop itself plus every handler an
# AdmittedMsg can reach synchronously on the dispatcher thread.
HOT_PATH: Dict[Tuple[str, str], Set[str]] = {
    ("tpubft/consensus/incoming.py", "Dispatcher"): {
        "_loop_body",
    },
    ("tpubft/consensus/replica.py", "Replica"): {
        "_on_admitted",
        "_dispatch_external",
        "_on_client_request",
        "_handle_client_request",
        "_post_admission",
        "_on_pre_prepare",
        "_on_share",
        "_handle_full_cert",
        "_on_checkpoint",
        "_on_time_opinion",
        "_on_ask_to_leave_view",
        "_on_view_change",
        "_on_new_view",
        "_on_restart_ready",
    },
}

FORBIDDEN_CALLS = {"unpack", "verify", "verify_batch"}

# span-allocation observability: per-message heap work the flight
# recorder replaces on the hot path (flight.record is the ONE allowed
# telemetry call in the handlers above)
FORBIDDEN_TELEMETRY = {"get_tracer", "start_span", "set_tag", "span"}


def _call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _functions(tree: ast.Module, class_name: str):
    for node in tree.body:
        if isinstance(node, ast.ClassDef) and node.name == class_name:
            for item in node.body:
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield item


def find_violations(root: str, hot_path=None, forbidden=None,
                    telemetry=None) -> List[Tuple[str, int, str]]:
    hot_path = HOT_PATH if hot_path is None else hot_path
    forbidden = FORBIDDEN_CALLS if forbidden is None else forbidden
    telemetry = FORBIDDEN_TELEMETRY if telemetry is None else telemetry
    out: List[Tuple[str, int, str]] = []
    for (rel, cls), fn_names in sorted(hot_path.items()):
        path = os.path.join(root, rel)
        with open(path, "rb") as f:
            tree = ast.parse(f.read(), filename=path)
        found: Set[str] = set()
        for fn in _functions(tree, cls):
            if fn.name not in fn_names:
                continue
            found.add(fn.name)
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) \
                        and _call_name(node) in forbidden:
                    out.append((
                        os.path.join(rel),
                        node.lineno,
                        f"{cls}.{fn.name} calls {_call_name(node)}() — "
                        f"hot-path handlers must consult the admission "
                        f"verdict / route through a _verify_* seam"))
                elif isinstance(node, ast.Call) \
                        and _call_name(node) in telemetry:
                    out.append((
                        os.path.join(rel),
                        node.lineno,
                        f"{cls}.{fn.name} calls {_call_name(node)}() — "
                        f"hot-path handlers may only emit telemetry "
                        f"through the bounded flight.record() API"))
                elif isinstance(node, ast.JoinedStr):
                    out.append((
                        os.path.join(rel),
                        node.lineno,
                        f"{cls}.{fn.name} builds an f-string — "
                        f"per-message string formatting is forbidden on "
                        f"the hot path; emit flight.record() events or "
                        f"%-style lazy log formatting"))
        for missing in sorted(fn_names - found):
            # a renamed handler silently leaving the lint's coverage is
            # itself a violation: the list must track the code
            out.append((rel, 0,
                        f"{cls}.{missing} not found — update "
                        f"tools/check_hotpath.py HOT_PATH"))
    return sorted(out)


def hot_path_size(hot_path=None) -> int:
    hot_path = HOT_PATH if hot_path is None else hot_path
    return sum(len(v) for v in hot_path.values())


def run(ctx) -> List[Finding]:
    findings: List[Finding] = []
    for rel, line, msg in find_violations(ctx.root):
        findings.append(Finding(PASS_ID, rel, line, f"{rel}:{msg[:60]}",
                                msg))
    return findings
