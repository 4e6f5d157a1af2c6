"""The seeded corpus and its records, written once per checkout.

``write_corpus`` is copied from ``chip_smoke.py``: a Zipf-weighted vocabulary
of made-up words in sentences — byte statistics a byte-level model learns
from within a few steps, no network, identical on every machine.  The
records are written by the program's own ``scripts/text2records.py`` into
many small files, so that ``--seed`` (the program's ``data_seed``) decides
which files, and so which windows, a run reads: the files outnumber the
program's interleave width, and the program shuffles the file list with
that seed.
"""
from __future__ import annotations

import os
import random
import shutil
import subprocess
import sys

from . import cell as cell_mod

#: the corpus is a fixed data set; a run's inputs are drawn from it by
#: ``--seed``
CORPUS_SEED = 20260926


def write_corpus(path: str, size: int, seed: int = CORPUS_SEED) -> None:
    rng = random.Random(seed)
    letters = "etaoinshrdlcumwfgypbvkjxqz"
    words = ["".join(rng.choices(letters, weights=range(26, 0, -1),
                                 k=rng.randint(2, 9))) for _ in range(4096)]
    weights = [1.0 / (i + 1) for i in range(len(words))]
    with open(path, "w") as f:
        written = 0
        while written < size:
            sentence = " ".join(rng.choices(words, weights=weights,
                                            k=rng.randint(4, 18)))
            line = sentence.capitalize() + rng.choice(".,.?!.") + \
                rng.choice(" \n")
            f.write(line)
            written += len(line)


def ensure_records(corpus_bytes: int, file_tokens: int, rehearsal: bool
                   ) -> str:
    """The glob of the record files for a corpus of ``corpus_bytes`` cut
    into files of ``file_tokens`` bytes; written on first use, found again
    by every later run of the checkout."""
    base = cell_mod.data_dir(rehearsal)
    final = os.path.join(base, f"corpus_{corpus_bytes}_{file_tokens}")
    if not os.path.isdir(final):
        tmp = f"{final}.{os.getpid()}.tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(os.path.join(tmp, "records"))
        corpus = os.path.join(tmp, "corpus.txt")
        write_corpus(corpus, corpus_bytes)
        done = subprocess.run(
            [sys.executable, os.path.join(cell_mod.ROOT, "scripts",
                                          "text2records.py"), corpus,
             "--output-dir", os.path.join(tmp, "records"), "--prefix",
             "bench", "--chunk-tokens", str(file_tokens)],
            cwd=cell_mod.ROOT, capture_output=True, text=True, timeout=600)
        if done.returncode != 0 or not os.listdir(os.path.join(tmp,
                                                               "records")):
            raise RuntimeError("text2records wrote no records:\n"
                               + done.stdout[-2000:] + done.stderr[-2000:])
        os.remove(corpus)
        try:
            os.rename(tmp, final)
        except OSError:
            shutil.rmtree(tmp, ignore_errors=True)   # another run won
    return os.path.join(final, "records", "*")
