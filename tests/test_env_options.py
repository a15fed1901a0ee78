"""`docs/OPTIONS.md` held to the package: every `OPENSEARCH_TPU_*` name the
package reads has a row (default, sort, and for a path switch who sets it
today), the table names nothing the package does not read, the count
does not grow unseen, and a name the document lists as retired is not read
again (ROADMAP D3). No JAX: the package is read as text."""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"OPENSEARCH_TPU_[A-Z0-9_]*")
SORTS = {"deployment setting", "path switch", "observability toggle",
         "test hook"}
CEILING = 15        # 46 when the table was made (PR 30); PR 46 retired 31


def _package_names() -> set:
    found = set()
    for base, _dirs, files in os.walk(os.path.join(ROOT, "opensearch_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(base, f)) as fh:
                    found.update(NAME.findall(fh.read()))
    return found


def _table() -> dict:
    """name -> (default, sort, set by), from the rows of the table."""
    rows = {}
    with open(os.path.join(ROOT, "docs", "OPTIONS.md")) as fh:
        for line in fh:
            cells = [c.strip() for c in line.strip().strip("|").split("|")]
            if len(cells) == 4 and NAME.fullmatch(cells[0].strip("`")):
                rows[cells[0].strip("`")] = tuple(cells[1:])
    return rows


def _retired() -> dict:
    """name -> what its value is now, from the rows under "Retired"."""
    rows = {}
    with open(os.path.join(ROOT, "docs", "OPTIONS.md")) as fh:
        for line in fh:
            cells = [c.strip() for c in line.strip().strip("|").split("|")]
            if len(cells) == 2 and NAME.fullmatch(cells[0].strip("`")):
                rows[cells[0].strip("`")] = cells[1]
    return rows


READ = sorted(_package_names())
TABLE = _table()
RETIRED = _retired()


@pytest.mark.parametrize("name", READ)
def test_an_option_the_package_reads_is_in_the_table(name):
    assert name in TABLE, f"{name} is read by the package and has no row " \
                          f"in docs/OPTIONS.md"
    default, sort, set_by = TABLE[name]
    assert default and sort in SORTS
    if sort == "path switch":
        assert set_by, f"{name}: a path switch's row says who sets it"


@pytest.mark.parametrize("name", sorted(RETIRED))
def test_a_retired_option_is_not_read_again(name):
    assert name not in READ, f"{name} was retired (docs/OPTIONS.md): the " \
                             f"package reads it again"
    assert name not in TABLE and RETIRED[name]


def test_the_table_names_nothing_the_package_does_not_read():
    assert sorted(set(TABLE) - set(READ)) == []


def test_the_count_does_not_grow_unseen():
    assert len(RETIRED) == 31
    assert len(READ) <= CEILING, \
        f"{len(READ)} options: a new one needs a decision, not a row " \
        f"(simplicity-review, Options; ROADMAP D3)"
    # the counts the document states below its table
    by_sort = {s: sum(1 for r in TABLE.values() if r[1] == s) for s in SORTS}
    with open(os.path.join(ROOT, "docs", "OPTIONS.md")) as fh:
        text = " ".join(fh.read().split())
    assert f"{len(TABLE)} names" in text
    for sort, n in by_sort.items():
        assert re.search(rf"\b{n} {sort}", text), (sort, n)
