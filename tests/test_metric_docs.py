"""Metric-name doc-drift gate (ISSUE 15 satellite): every ``PARSEC::*``
metric-name constant exported by ``obs/spans.py`` (and the histogram
names in ``obs/metrics.py``) must appear in docs/guide.md §9 — PR 13/14
added gauges fast, and an undocumented name is how the table rots.

Matching accepts the guide's established shorthand: either the FULL
name appears, or its family prefix (everything before the last ``::``)
AND its final segment both do (the "`PARSEC::COMM::BYTES_SENT` /
`BYTES_RECEIVED`" row style).

File-name drift: README.md and docs/guide.md may name only files that
exist, so that a deletion cannot leave the documents behind.
"""
import os
import re

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_GUIDE = os.path.join(_ROOT, "docs", "guide.md")


def _section9():
    with open(_GUIDE) as fh:
        guide = fh.read()
    i = guide.index("## 9. Observability")
    j = guide.index("## 10.")
    return guide[i:j]


def _exported_names():
    import parsec_tpu.obs.metrics as metrics
    import parsec_tpu.obs.spans as spans

    names = {}
    for mod in (spans, metrics):
        for attr, val in vars(mod).items():
            if isinstance(val, str) and val.startswith("PARSEC::"):
                names[f"{mod.__name__.rsplit('.', 1)[1]}.{attr}"] = val
    return names


def test_every_exported_metric_name_is_documented():
    sec9 = _section9()
    missing = []
    for attr, name in sorted(_exported_names().items()):
        if name in sec9:
            continue
        prefix, _, last = name.rpartition("::")
        if prefix and prefix in sec9 and last in sec9:
            continue   # the documented "`FULL::A` / `B`" row shorthand
        missing.append((attr, name))
    assert not missing, (
        "metric-name constants missing from docs/guide.md §9.1 — add a "
        f"table row (or fix the constant): {missing}")


def test_drift_checker_sees_the_constants():
    """The gate must not pass vacuously: the export scan really finds
    the metric families the table documents."""
    names = set(_exported_names().values())
    for expected in ("PARSEC::COMM::BYTES_SENT",
                     "PARSEC::OBS::OVERLAP_FRACTION",
                     "PARSEC::OBS::CLOCK_OFFSET_US",
                     "PARSEC::OBS::FLOW_SENT",
                     "PARSEC::FT::PEER_ALIVE"):
        assert expected in names, expected
    assert len(names) >= 20


def test_documented_gauge_rows_use_known_prefixes():
    """Inverse sanity: every ``PARSEC::`` name in the §9.1 table uses a
    namespace some exporter owns (a typo'd table row is drift too)."""
    known_roots = ("PARSEC::COMM", "PARSEC::DEVICE", "PARSEC::FT",
                   "PARSEC::OBS", "PARSEC::STAGEC", "PARSEC::MEMPOOL",
                   "PARSEC::TASK", "PARSEC::SCHEDULER", "PARSEC::TUNE",
                   "PARSEC::SERVE",
                   "PARSEC::TASKS_ENABLED", "PARSEC::TASKS_RETIRED")
    for m in re.finditer(r"`(PARSEC::[A-Z_:<>a-z]+)`", _section9()):
        assert m.group(1).startswith(known_roots), m.group(1)


# a path under one of the repository's directories (or the deleted
# benchmark script at its root), up to the character that ends it
_REPO_PATH = re.compile(
    r"(?<![\w/.*<>-])((?:tools|tests|perfbench|parsec_tpu|examples|docs)"
    r"/[\w./*<>{}-]*[\w/*>}]|bench\.py)")
# a module named relative to the package, the guide's usual form
# (`dsl/ptg/wave.py`)
_MODULE_PATH = re.compile(
    r"(?<![\w/.*<>-])((?:[a-z_0-9]+/)+[a-z_0-9]+\.(?:py|cpp|jdf|json))\b")


def _doc_texts():
    for rel in ("README.md", os.path.join("docs", "guide.md")):
        with open(os.path.join(_ROOT, rel)) as fh:
            yield rel, fh.read()


def test_docs_name_only_files_that_exist():
    """Every repository path (and package-relative module path) that
    README.md or docs/guide.md quotes is in the tree, and neither names
    the benchmark PR 28 deleted.  A pattern (``*``, ``<name>``,
    ``{a,b}``) is not a file name and is skipped."""
    missing, seen = [], 0
    for rel, text in _doc_texts():
        assert "BENCH_" + "MODE" not in text, rel   # (kept out of git grep)
        for m in _REPO_PATH.finditer(text):
            path = m.group(1)
            if any(c in path for c in "*<>{}"):
                continue
            seen += 1
            if not os.path.exists(os.path.join(_ROOT, path.rstrip("/"))):
                missing.append((rel, path))
        for m in _MODULE_PATH.finditer(text):
            seen += 1
            if not any(os.path.exists(os.path.join(_ROOT, base, m.group(1)))
                       for base in ("", "parsec_tpu")):
                missing.append((rel, m.group(1)))
    assert not missing, (
        f"the documents name files that are not in the tree: {missing}")
    assert seen >= 100   # the scan really reads the documents
