"""The names the benchmark reads are names the program emits.

A per-layer metric of reader kind ``program``, ``ledger_phase`` or
``trace_scope`` (``benchmark/layers/*.json``) names spans, counters,
ledger phases and ``jax.named_scope`` scopes that live in
``kueue_oss_tpu/``. A PR that deletes or renames one turns the metric
``null`` on the chip, after the PR is accepted. This guard reads the
layer files (read-only) and the package's sources (an ``ast`` walk: no
device, no import of the program) and fails with the metric's name and
the missing literal.
"""

import ast
import functools
import json
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
LAYERS = ROOT / "benchmark" / "layers"
PACKAGE = ROOT / "kueue_oss_tpu"

#: reader kinds whose arguments are the program's own names
PROGRAM_READERS = ("program", "ledger_phase", "trace_scope")
#: ``per`` values that are facts of the window, not names of the program
WINDOW_FACTS = ("window_s", "passes")
#: ``spans.<call>("name", ...)`` -> the kind of name its literal is
SPAN_CALLS = {"span": "timed", "add": "timed", "add_since": "timed",
              "count": "counted"}


def _literal(node) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


@functools.lru_cache(maxsize=None)
def emitted() -> dict:
    """kind -> the names the package's sources emit:

    ``timed``    ``spans.span`` / ``spans.add`` / ``spans.add_since``
                 (a total by name: seconds and count)
    ``counted``  ``spans.count``, and what ``obs/spans.py`` counts
                 itself from JAX's events (``counters()``)
    ``phases``   a ledger row's ``phases`` keys: the spans under a
                 collecting span, and the engine's columnar split
                 (``SolverEngine._export_split``)
    ``scopes``   ``jax.named_scope``
    """
    out = {k: set() for k in ("timed", "counted", "phases", "scopes")}
    for path in sorted(PACKAGE.rglob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Attribute) and node.args:
                name = _literal(node.args[0])
                if name is None:
                    continue
                owner = node.func.value
                if (isinstance(owner, ast.Name) and owner.id == "spans"
                        and node.func.attr in SPAN_CALLS):
                    out[SPAN_CALLS[node.func.attr]].add(name)
                    if node.func.attr == "span":
                        out["phases"].add(name)
                elif node.func.attr == "named_scope":
                    out["scopes"].add(name)
            elif isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Dict) and any(
                    isinstance(t, ast.Attribute)
                    and t.attr == "_export_split" for t in node.targets):
                # names built at run time, resolved here: the ledger
                # row takes the keys of this dict as phases
                out["phases"].update(
                    k for k in map(_literal, node.value.keys) if k)
    # obs/spans.py counters(): ``for key in ("retrace_s", ...):
    # out[key] = ...`` beside the boundary counters
    tree = ast.parse((PACKAGE / "obs" / "spans.py").read_text())
    fn = next(n for n in ast.walk(tree)
              if isinstance(n, ast.FunctionDef) and n.name == "counters")
    for node in ast.walk(fn):
        if isinstance(node, ast.For) and isinstance(node.iter, ast.Tuple):
            out["counted"].update(
                k for k in map(_literal, node.iter.elts) if k)
    return out


def wanted(layer: dict) -> list:
    """(argument, name, kinds that satisfy it) for one layer file."""
    args = layer.get("args", {})
    reader = layer["reader"]
    want = []
    if reader == "program":
        for arg in ("spans", "minus"):
            want += [(arg, n, ("timed",)) for n in args.get(arg, ())]
        # a count is a counter's, or a span's count (readers._count_of)
        for arg in ("count", "per"):
            n = args.get(arg)
            if n is not None and n not in WINDOW_FACTS:
                want.append((arg, n, ("counted", "timed")))
    elif reader == "ledger_phase":
        # ``per`` of this kind is a fact of the window or one of the
        # benchmark's own counters, never a name of the program
        for arg in ("phases", "minus_phases"):
            want += [(arg, n, ("phases",)) for n in args.get(arg, ())]
    elif reader == "trace_scope":
        want += [("scopes", n, ("scopes",)) for n in args.get("scopes", ())]
    return want


def missing(layer: dict, names: dict) -> list:
    return [f"{layer['name']}: args.{arg} names {n!r}, which no "
            f"{' / '.join(kinds)} name under kueue_oss_tpu/ is"
            for arg, n, kinds in wanted(layer)
            if not any(n in names[k] for k in kinds)]


def _layers() -> list:
    out = []
    for path in sorted(LAYERS.glob("*.json")):
        layer = json.loads(path.read_text())
        layer.setdefault("name", path.stem)
        if layer["reader"] in PROGRAM_READERS:
            out.append(layer)
    return out


@pytest.mark.parametrize("layer", _layers(), ids=lambda la: la["name"])
def test_layer_reads_names_the_program_emits(layer):
    assert wanted(layer), f"{layer['name']} names nothing of the program"
    assert not missing(layer, emitted())


def test_guard_fails_on_a_renamed_span_phase_or_scope():
    """The guard's own check: take one literal of each kind away (as a
    rename in the package would) and every layer that names it fails,
    by metric and literal."""
    layers = _layers()
    assert len(layers) >= 29
    for kind, gone, metric in (("timed", "apply.commit", "apply_commit_share"),
                               ("counted", "retrace_s", "retrace_share"),
                               ("counted", "flush_queues",
                                "flush_queues_per_request"),
                               ("counted", "flush_requests",
                                "flush_queues_per_request.stream"),
                               ("counted", "flush_rows",
                                "flush_rows_per_request"),
                               ("counted", "scan_victim_entries",
                                "scan_victim_entry_share"),
                               ("counted", "scan_entries",
                                "scan_victim_entry_share"),
                               ("phases", "device_put",
                                "encode_put_s_per_pass"),
                               ("scopes", "classical_search",
                                "kernel_search_share")):
        names = {k: set(v) for k, v in emitted().items()}
        assert gone in names[kind]
        names[kind].discard(gone)
        found = [m for la in layers for m in missing(la, names)]
        assert any(m.startswith(f"{metric}: ") and repr(gone) in m
                   for m in found), (kind, gone, found)
