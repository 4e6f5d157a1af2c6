"""``tests/pins/`` is whole (PR 74): no digest in ``traces.json`` that no test
asks for and no ``harness.pinned`` call whose name the file does not hold, a
start-up pin for every configuration file and none beside them, and a
``seconds.json`` that names only files that exist.  The names are read from
the tests' source, so a call gives its name as a string first — ``"a/b"``,
``"a/" + part`` or ``f"a/{part}"``."""
import glob
import json
import os
import re

from harness import PINS, REPO, config_files

_CALL = re.compile(r'harness\.pinned\(\s*f?"([^"]+)"(\s*\+)?')


def _asked_for():
    """A pattern a call of ``harness.pinned`` in ``tests/*.py``; each call
    has one."""
    patterns = []
    for path in sorted(glob.glob(os.path.join(REPO, "tests", "*.py"))):
        with open(path) as f:
            text = f.read()
        calls = _CALL.findall(text)
        assert len(calls) == len(re.findall(r"harness\.pinned\(", text)), path
        patterns += [re.sub(r"\\\{[^}]*\}", ".+", re.escape(name))
                     + (".+" if plus else "") for name, plus in calls]
    return patterns


def _traces():
    with open(os.path.join(PINS, "traces.json")) as f:
        return json.load(f)


def every_pinned_trace_is_asked_for_test():
    patterns = _asked_for()
    orphans = [name for name in _traces()
               if not any(re.fullmatch(p, name) for p in patterns)]
    assert not orphans


def every_name_asked_for_is_pinned_test():
    names = list(_traces())
    missing = [p for p in _asked_for()
               if not any(re.fullmatch(p, name) for name in names)]
    assert len(names) > 40 and not missing


def a_digest_is_a_sha1_test():
    assert all(re.fullmatch(r"[0-9a-f]{40}", digest)
               for digest in _traces().values())


def every_configuration_file_has_its_startup_pin_and_no_other_test():
    held = sorted(os.path.relpath(path, os.path.join(PINS, "startup"))
                  for path in glob.glob(os.path.join(PINS, "startup", "**",
                                                     "*.json"),
                                        recursive=True))
    assert held == config_files() and len(held) > 30


def the_order_names_only_files_that_exist_test():
    with open(os.path.join(PINS, "seconds.json")) as f:
        seconds = json.load(f)
    assert len(seconds) > 50
    assert not [path for path in seconds
                if not os.path.isfile(os.path.join(REPO, path))]
