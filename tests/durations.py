"""Where a run's time went, by file: what ``tests/conftest.py`` prints at the
end of a run, from the reports the controller holds — and, run as a script on
the run's junit file, every file's seconds as ``tests/pins/seconds.json``
holds them (the order of the next run: ``python tests/durations.py
/tmp/_t1.xml > tests/pins/seconds.json``)."""
import collections
import json
import sys
import xml.etree.ElementTree


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


def seconds_by_file(junit_path: str) -> dict:
    """``{"tests/<file>.py": seconds}`` of a run's junit file, the longest
    first."""
    cases = xml.etree.ElementTree.parse(junit_path).iter("testcase")
    _, files = longest_files(
        ((case.get("classname").replace(".", "/") + ".py",
          float(case.get("time"))) for case in cases), top=None)
    return {path: round(spent, 1) for spent, _, path in files}


if __name__ == "__main__":
    json.dump(seconds_by_file(sys.argv[1]), sys.stdout, indent=1)
    print()
