"""One benchmark, one account of speed (PR 29).

The repo's judged speed is the chip's: ``benchmark/`` is what measures it,
``PERF_LEDGER.jsonl`` and ``PERF.md`` are the record. The second benchmark
and its record files are gone; the documents a reader meets first must not
send anyone back to them, and a probe a document cites must be in the tree.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the retired benchmark driver and its two families of record files
_RETIRED = re.compile(r"bench\.py|BENCH_r|MULTICHIP_r")
_PROBE = re.compile(r"experiments/(\w+\.py)")


def _rel(pattern):
    return sorted(os.path.relpath(p, REPO) for p in
                  glob.glob(os.path.join(REPO, pattern), recursive=True))


_DOCS = _rel("docs/*.md")
_READER_FACING = ["README.md", "examples/README.md", "run_tests.sh",
                  ".claude/skills/verify/SKILL.md"] + _DOCS


def _read(rel):
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        return f.read()


@pytest.mark.parametrize("rel", _READER_FACING)
def test_names_no_retired_measurement(rel):
    hits = [f"{rel}:{n}: {line.strip()}"
            for n, line in enumerate(_read(rel).splitlines(), 1)
            if _RETIRED.search(line)]
    assert not hits, "\n".join(hits)


def test_cited_probes_exist():
    citing = ["README.md", "PERF.md"] + _DOCS + _rel("hivemall_tpu/**/*.py")
    cited = {(name, rel) for rel in citing
             for name in _PROBE.findall(_read(rel))}
    assert cited, "no probe cited anywhere: the pattern no longer matches"
    missing = sorted(f"{rel} cites experiments/{name}"
                     for name, rel in cited
                     if not os.path.exists(
                         os.path.join(REPO, "experiments", name)))
    assert not missing, "\n".join(missing)
