"""The documents a new owner is sent to name only files that exist.

One case per document: every repo path it spells (``scripts/x.py``,
``homebrewnlp_tpu/a/b.py``, ``infer/engine.py``, ``configs/x.json``, a bare
``main.py`` or ``BASELINE.json``) is a file of this checkout — whole, or as
the tail of one file's path.  History documents (``CHANGES.md``, ``PERF.md``,
``ROADMAP.md``, ``BASELINE.md``, ``SURVEY.md``) tell what was and are out of scope; a document in scope that
is history until its rewrite says so in its head (``HISTORY_HEADER``).
"""
import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (["README.md", "COMPONENTS.md", ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, REPO)
                      for p in glob.glob(os.path.join(REPO, "docs", "*.md"))))

#: scratch the program and its tools make (.gitignore): a deleted file's
#: copy there must not count as existing
SCRATCH_DIRS = {".git", "__pycache__", ".jax_cache", ".pytest_cache",
                "./runs", "./data", "./chiprun_out", "./chip_smoke_out",
                "./buffer_configs", "./benchmark/out"}

#: files a run writes or the reader brings, and HomebrewNLP-MTF's own
#: (``src/...``, which COMPONENTS.md and docs/MIGRATION.md map to this
#: repo's): not files of the checkout
NOT_OF_THE_CHECKOUT = {
    "report.json", "config.json", "cfg.json", "foo.json", "preempt.json",
    "leases.json", "index.json", "tokenizer.json",
    "context.py", "convolution.py", "dataclass.py", "momentumnet.py",
    "mtf_wrapper.py", "revnet.py", "tf_wrapper.py", "utils_core.py",
    "utils_mtf.py", "text2tfrecord.py", "video2tfrecord.py",
}

#: docs/PERFORMANCE.md (ROADMAP.md D9) carries this in its first lines
HISTORY_HEADER = "files named here may be gone"

_URL = re.compile(r"https?://\S+")
_PATH = re.compile(r"(?<![\w/.<>~$*{}-])((?:[\w.-]+/)*[\w-]+(?:\.[\w-]+)*"
                   r"\.(?:py|json|sh))(?![\w/*])")


@functools.lru_cache(maxsize=None)
def _repo_files():
    out = []
    for dirpath, dirnames, filenames in os.walk(REPO):
        rel = os.path.relpath(dirpath, REPO)
        dirnames[:] = [d for d in dirnames if not SCRATCH_DIRS & {
            d, "./" + os.path.normpath(os.path.join(rel, d))}]
        out += ["/" + os.path.normpath(os.path.join(rel, f))
                for f in filenames]
    return out


def named_paths(text: str):
    return sorted({m.group(1) for m in _PATH.finditer(_URL.sub("", text))})


@pytest.mark.parametrize("document", DOCUMENTS)
def documents_name_only_files_that_exist_test(document):
    files = _repo_files()
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    if HISTORY_HEADER in " ".join(text.splitlines()[:8]):
        return
    names = named_paths(text)
    assert names, f"{document} names no file: the pattern is broken"
    missing = [n for n in names
               if os.path.basename(n) not in NOT_OF_THE_CHECKOUT
               and not n.startswith("src/")
               and not any(p.endswith("/" + n) for p in files)]
    assert missing == [], (
        f"{document} names files this checkout does not have: {missing}")


def named_paths_negative_control_test():
    text = ("run `scripts/gone.py`, then infer/engine.py; see "
            "https://example.org/x/config.json and <run>/report.json")
    assert named_paths(text) == ["infer/engine.py", "scripts/gone.py"]


def a_runs_seconds_fold_by_file_test():
    """What the end of a run's log says (``tests/conftest.py``, README
    'Tests'): a pair a phase of a test, summed by file, the longest first."""
    from durations import longest_files
    total, files = longest_files([
        ("tests/a_test.py::x_test[1]", 2.0), ("tests/b_test.py::y_test", 4.5),
        ("tests/a_test.py::x_test[1]", 0.5), ("tests/a_test.py::x_test[2]", 1),
        ("tests/c_test.py::z_test", 0.25)], top=2)
    assert total == 8.25
    assert files == [(4.5, 1, "tests/b_test.py"), (3.5, 2, "tests/a_test.py")]
    assert longest_files([]) == (0, [])
