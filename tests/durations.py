"""Where a run's time went, by file: what ``tests/conftest.py`` prints at the
end of a run, from the reports the controller holds."""
import collections


def longest_files(durations, top: int = 10):
    """``(total seconds, [(seconds, tests, file), ...])`` of ``(nodeid,
    seconds)`` pairs — a pair a phase of a test — the ``top`` longest files
    first."""
    seconds, tests = collections.Counter(), collections.defaultdict(set)
    for nodeid, spent in durations:
        path = nodeid.split("::")[0]
        seconds[path] += spent
        tests[path].add(nodeid)
    return sum(seconds.values()), [(spent, len(tests[path]), path)
                                   for path, spent in seconds.most_common(top)]
