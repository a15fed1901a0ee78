"""The documents held to the tree: a tracked document that names a `*.py`
or `*.json` file which is not in the tree fails, unless the document's own
command writes the file, the program writes it at run time, or the
paragraph says the file was deleted. PR 30 deleted a second benchmark with
thirteen result files, and a dozen documents still sent their reader to
it: this keeps the two together from here on. `CHANGES.md` and `PERF_LEDGER.jsonl` are history and exempt. No
JAX: everything is read as text."""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = (["README.md", "ROADMAP.md", "PERF.md", "SURVEY.md",
              ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, ROOT) for p in
                      glob.glob(os.path.join(ROOT, "docs", "*.md"))))
# a file name, with the directories written before it
NAMED = re.compile(r"(?<![\w/.*<>{}-])((?:[\w.-]+/)*[\w.-]+\.(?:py|json))"
                   r"(?![\w*/-])")
# what a run leaves behind and `.gitignore` lists: never in a checkout
NOT_TRACKED = {"chiprun_out", "benchmark_out", "__pycache__"}


def _tree() -> list:
    """Every file of the checkout, as a path from its root (dot
    directories are scratch or git's, but for `.claude`)."""
    out = []
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in NOT_TRACKED
                   and (not d.startswith(".") or d == ".claude")]
        out += [os.path.relpath(os.path.join(base, f), ROOT) for f in files]
    return out


TREE = _tree()


def _in_tree(name: str) -> bool:
    """`name` is a file of the tree, or the tail of one's path (documents
    write `search/fastpath.py` for `opensearch_tpu/search/fastpath.py`)."""
    return any(p == name or p.endswith("/" + name) for p in TREE)


def _made_by_the_program(name: str) -> bool:
    """A file the program itself writes under a data path at run time
    (`meta.json` of a segment): its source has the name as a literal."""
    literal = re.compile("[\"']" + re.escape(os.path.basename(name))
                         + "[\"']")
    for path in TREE:
        if path.startswith("opensearch_tpu/") and path.endswith(".py"):
            with open(os.path.join(ROOT, path)) as fh:
                if literal.search(fh.read()):
                    return True
    return False


def _written_by_the_documents_own_command(name: str, text: str) -> bool:
    """`--json out.json`, `-o x.json`, `> x.json`: the document tells its
    reader how the file comes to be."""
    return re.search(r"(?:--json|--out|-o|>)\s+`?" + re.escape(name), text) \
        is not None


def missing_files(text: str) -> list:
    """The `*.py` / `*.json` names in `text` that are no file of the tree."""
    found = set()
    for paragraph in re.split(r"\n\s*\n", text):
        if re.search(r"deleted by PR \d+", paragraph, re.I):
            continue            # history, and it says so
        for name in NAMED.findall(paragraph):
            if not (_in_tree(name) or _made_by_the_program(name)
                    or _written_by_the_documents_own_command(name, text)):
                found.add(name)
    return sorted(found)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_a_document_names_only_files_that_exist(document):
    with open(os.path.join(ROOT, document)) as fh:
        assert missing_files(fh.read()) == []


def test_the_rule_sees_a_deleted_file_and_spares_what_it_should():
    assert _in_tree("chip_smoke.py") and _in_tree("search/fastpath.py")
    text = ("Run `python retired.py` and `scripts/retired_x.py`; the ladder "
            "is `RESULTS_r07.json`, patterns are `RESULTS_r*.json`, "
            "`<kind>.py`, `retired_{a,b}.py`.\n\n"
            "`gone.py` was deleted by PR 30.\n\n"
            "`python scripts/traffic_harness.py --json out.json`, "
            "`chip_smoke.py`, `benchmark/run.py`, `BENCHMARK.json`.")
    assert missing_files(text) == ["RESULTS_r07.json", "retired.py",
                                   "scripts/retired_x.py"]


def test_the_package_names_no_script_or_result_file_that_is_gone():
    gone = []
    for path in TREE:
        if not (path.startswith("opensearch_tpu/") and path.endswith(".py")):
            continue
        with open(os.path.join(ROOT, path)) as fh:
            text = fh.read()
        names = re.findall(r"scripts/[\w.-]+\.py", text) + re.findall(
            r"\b(?:BENCH|MULTICHIP|MESH_SHARE)_[\w.*-]*\.json", text)
        gone += [f"{path}: {n}" for n in names if not _in_tree(n)]
    assert gone == []
