"""The documents cite files that exist.

A document that names a tool, a test, a module or a record in the root
that the checkout does not hold describes a repository that is gone (the
eleven bench scripts and their 25 CPU records were cited by 50 lines for
22 PRs after the benchmark replaced them). One case a document.
"""
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (["README.md", "PARITY.md", ".github/workflows/ci.yml",
              ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, ROOT)
                      for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))))

# a path of the checkout: it starts a word (``~/.nnstreamer_tpu/models.json``
# is a file in a home directory, not one of ours) and names no placeholder
PATH = re.compile(r"(?<![\w./~<>*-])"
                  r"((?:tools|benchmark|tests|nnstreamer_tpu)/[\w./-]*"
                  r"\.(?:py|json|md))\b")
# a round's record in the root: WIREFUZZ_r19.json, a suite's rows as .jsonl
RECORD = re.compile(r"(?<![\w/])([A-Z][A-Z_]*_r\d\d\.jsonl?)\b")


def cited(text: str) -> set:
    return set(PATH.findall(text)) | set(RECORD.findall(text))


def test_the_pattern_finds_what_a_document_cites():
    text = ("run: python tools/chaos.py --smoke\n"
            "`tests/test_aot.py::TestExport` and (`benchmark/lib/peaks.py`), "
            "`WIREFUZZ_r19.json`; not `~/.nnstreamer_tpu/models.json`, "
            "`tests/test_*.py` or `benchmark/layer_metrics/<stem>.py`")
    assert cited(text) == {"tools/chaos.py", "tests/test_aot.py",
                           "benchmark/lib/peaks.py", "WIREFUZZ_r19.json"}


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_cites_exists(document):
    with open(os.path.join(ROOT, document), encoding="utf-8") as fh:
        gone = sorted(p for p in cited(fh.read())
                      if not os.path.exists(os.path.join(ROOT, p)))
    assert not gone, f"{document} cites files that do not exist: {gone}"
