"""The documents a new owner reads first name things that exist.

Nothing else checks a path quoted in prose: a README that sends its reader
to a deleted tool passes every other test.  tpulint P7 already holds the
README's flag and variable tables to the code both ways; this file holds
the paths, and the one way the README says speed is measured."""

import json
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

# the harnesses BENCHMARK.json superseded, deleted in PR 30
DELETED = ("bench.py", "bench_sweep", "bench_serving", "load_test.py",
           "profile_step", "diag_prefill", "bench_r0")

# a quoted token is a path of this repo when it starts in one of its trees,
# or is a top-level script or record
_TREES = ("tpuserve/", "tools/", "tests/", "benchmark/", "native/")
# ... or in a directory of tpuserve/ or benchmark/ written without its parent
_SUBTREES = tuple(
    f"{d.name}/" for parent in ("tpuserve", "benchmark")
    for d in sorted((REPO / parent).iterdir())
    if d.is_dir() and not d.name.startswith(("_", ".")))
_TOP_LEVEL = re.compile(r"^[A-Za-z_][\w.-]*\.(py|json|jsonl)$")
# what a path is resolved against: the README writes `runtime/slo.py`,
# PERF.md `harness/plan.py`
_ROOTS = (REPO, REPO / "tpuserve", REPO / "benchmark")
# written by a run or a deployment, never committed
_GENERATED = {"host_spans.steps.json", "incident.json", "workload.json",
              "report.json", "trace.json", "perfetto_trace.json"}


def _quoted(text):
    """Tokens in backticks, and the words of fenced code blocks."""
    for block in re.findall(r"```.*?\n(.*?)```", text, re.S):
        yield from block.split()
    text = re.sub(r"```.*?```", "", text, flags=re.S)
    for span in re.findall(r"`([^`\n]+)`", text):
        yield from span.split()


def _as_path(token):
    """The repo path a quoted token claims to be, or None."""
    token = token.strip("()[],;:'\"")
    token = re.split(r"[:#]", token)[0]             # file.py:123, file.py::test
    token = token.rstrip(".")
    if not token or any(c in token for c in "*<>{}$|=…") or "..." in token:
        return None                                 # a pattern, not a name
    if token.startswith(_TREES + _SUBTREES):
        return token
    if _TOP_LEVEL.match(token) and token not in _GENERATED:
        return token
    return None


def _exists(path):
    if "/" not in path:
        # a bare file name: the repo's own, wherever it lives
        return any(REPO.glob(path)) or any(
            p for tree in _TREES for p in (REPO / tree).rglob(path))
    # `runtime/faults.SITES`: a name inside the module
    module = re.sub(r"\.[A-Za-z_]\w*$", ".py", path)
    return any((root / p).exists() for root in _ROOTS
               for p in (path, module))


def _sections(text, first, last):
    """``## <first>.`` up to (not including) the section after ``## <last>.``"""
    start = re.search(rf"^## {first}\. ", text, re.M).start()
    end = re.search(rf"^## {last + 1}\. ", text, re.M).start()
    return text[start:end]


DOCS = {
    "README.md": lambda t: t,
    "PARITY.md": lambda t: t,
    ".claude/skills/verify/SKILL.md": lambda t: t,
    # §6 and §7 are history: they may name what a PR deleted
    "PERF.md": lambda t: _sections(t, 1, 5),
}


@pytest.mark.parametrize("doc", list(DOCS))
def test_named_paths_exist(doc):
    text = DOCS[doc]((REPO / doc).read_text())
    named = {p for p in map(_as_path, _quoted(text)) if p}
    assert named, f"{doc}: the extraction found no path at all"
    missing = sorted(p for p in named if not _exists(p))
    assert not missing, f"{doc} names what is not in the repo: {missing}"


def test_no_source_names_a_deleted_tool():
    me = pathlib.Path(__file__).resolve()
    files = [REPO / "chip_smoke.py"]
    for tree in ("tpuserve", "tools", "tests"):
        files += [p for p in (REPO / tree).rglob("*")
                  if p.is_file() and p.suffix in (".py", ".cc", ".h", ".md",
                                                  ".json", ".toml", ".yaml")
                  and p.resolve() != me]
    hits = [f"{p.relative_to(REPO)}:{n}: {name}"
            for p in files
            for n, line in enumerate(p.read_text(errors="replace")
                                     .splitlines(), 1)
            for name in DELETED if name in line]
    assert not hits, hits


def test_readme_measures_with_the_benchmark():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    readme = (REPO / "README.md").read_text()
    start = re.search(r"^## Measuring$", readme, re.M)
    assert start, "README.md has no '## Measuring' section"
    section = readme[start.end():]
    section = section[:re.search(r"^## ", section, re.M).start()]
    assert " ".join(bench["command"]) in section
    for cell in bench["workloads"]:
        assert f"`{cell['name']}`" in section, cell["name"]
    assert "PERF_LEDGER.jsonl" in section
    # and nowhere does the README offer another way
    for name in DELETED:
        assert name not in readme, name
