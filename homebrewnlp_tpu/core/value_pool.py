"""The host's cores, all of them, for ``Model.init``'s parameter values.

A parameter's value depends on nothing but its name, the seed and its sizes
(``core/scope.name_seed``: one Philox stream per name), and the init-mode
graph walk is abstract, so the walk only hands each value in as a job and
goes on; the jobs run here, largest first, on as many threads as the process
has cores, and ``finish`` returns them when the last is made.  numpy's
generator fills, LAPACK's QR and the casts release the interpreter lock.

Why BLAS is held to ONE thread per call while the pool lives: OpenBLAS's own
threads buy a tall-skinny QR nothing, and a pool of callers each fanning out
to every core oversubscribes them.  Eight QRs of (8192, 512), the flagship's
commonest shape, numpy 2.0.2 / OpenBLAS 0.3.27, on the 8-core sandbox
(ISSUE 25) | on the v5e's 13-core host (my chip run, PR 25):

    one after another, BLAS at its default threads    7.2 |  5.35 s
    one after another, BLAS held to 1                 8.4 |  6.58 s
    8 threads, BLAS at its default threads EACH      33.3 | 30.25 s  <- the trap
    8 threads, BLAS held to 1                         2.1 |  1.12 s

What one BLAS thread costs: threaded OpenBLAS rounds a few elements of a QR
differently (its thread count moves the edges of the blocks its kernels
work on), so about 3% of the flagship's values differ from the ones the
default thread count gives on the same host, each in one element of
millions, by one ulp.  What it buys besides the time: the default IS the
host's core count, so the values used to differ between hosts in the same
way; held to one thread they are the same on every host that can hold it.

The serial fallback observes two things and takes no option: one usable core
(``os.sched_getaffinity``), or no handle on the loaded BLAS's thread count
(``threadpoolctl`` missing, or finding no BLAS library to hold).  Either way
``width`` is 1, no thread is started, and ``finish`` makes the values one
after another on the caller's thread, as ``Model.init`` did before the pool.
"""
from __future__ import annotations

import contextlib
import itertools
import math
import os
import queue
import threading
import time
import typing

import numpy as np


def usable_cores() -> int:
    """Cores this process may run on (its affinity mask, not the machine's
    count)."""
    return len(os.sched_getaffinity(0))


def blas_held_to_one_thread():
    """A context manager that holds every loaded BLAS to one thread per call
    and restores what it found, or None where there is no such handle."""
    try:
        import threadpoolctl
    except ImportError:
        return None
    blas = threadpoolctl.ThreadpoolController().select(user_api="blas")
    if not blas.lib_controllers:        # no library found to hold
        return None
    return blas.limit(limits=1)


class ValuePool:
    """``with ValuePool() as pool: pool.submit(...)...; values =
    pool.finish()``.  ``width`` threads make values at once: ``width - 1``
    workers from the first ``submit`` on, plus the caller inside ``finish``.
    Leaving the block joins every worker and restores BLAS's thread count,
    whatever was raised; jobs not yet started are then dropped."""

    def __init__(self):
        self._jobs: queue.PriorityQueue = queue.PriorityQueue()
        self._order = itertools.count()     # ties: first handed in first
        self._threads: typing.List[threading.Thread] = []
        self._values: typing.Dict[str, np.ndarray] = {}
        self._errors: typing.List[BaseException] = []
        self._stop = threading.Event()
        self._held = contextlib.ExitStack()
        self.width = 1
        self.jobs = 0
        self.cpu_seconds = 0.0
        self._cpu_lock = threading.Lock()

    def __enter__(self) -> "ValuePool":
        cores = usable_cores()
        held = blas_held_to_one_thread() if cores > 1 else None
        if held is not None:
            self._held.enter_context(held)
            self.width = cores
        return self

    def __exit__(self, *exc) -> bool:
        self._stop.set()
        try:
            self._release_workers()
        finally:
            self._held.close()
        return False

    def submit(self, name: str, size: int,
               make: typing.Callable[[], np.ndarray]) -> None:
        """Hand in the job that makes ``name``'s value (``size`` elements:
        larger jobs start first)."""
        self.jobs += 1
        self._jobs.put((-size, next(self._order), name, make))
        if len(self._threads) < self.width - 1:
            t = threading.Thread(target=self._drain, daemon=True,
                                 name=f"init-values-{len(self._threads)}")
            self._threads.append(t)
            t.start()

    def finish(self) -> typing.Dict[str, np.ndarray]:
        """Make what is left alongside the workers, wait for the last value
        and return them all by name; the first job that raised raises here."""
        self._release_workers(help_out=True)
        if self._errors:
            raise self._errors[0]
        return self._values

    @property
    def workers(self) -> int:
        """Threads that could have had a job: the width, capped by the
        number of jobs; 1 = the serial path."""
        return max(1, min(self.width, self.jobs))

    def _release_workers(self, help_out: bool = False):
        for _ in self._threads:
            self._jobs.put((math.inf, next(self._order), None, None))
        if help_out:
            self._jobs.put((math.inf, next(self._order), None, None))
            self._drain()
        for t in self._threads:
            t.join()
        self._threads = []

    def _drain(self):
        while True:
            _, _, name, make = self._jobs.get()
            if make is None:            # the walk is over and the queue dry
                return
            if self._stop.is_set():     # a job or the walk raised: drop it
                continue
            t0 = time.thread_time()
            try:
                self._values[name] = make()
            except Exception as e:      # raised again by finish()
                self._errors.append(e)
                self._stop.set()
            finally:
                with self._cpu_lock:
                    self.cpu_seconds += time.thread_time() - t0
