"""Tier-1 lint: every ModelParameter knob has a docs/CONFIG.md row
(analysis/ast_lint.py's config-docs rule — PRs 1-3 hand-maintained this
invariant; now it is mechanical)."""
from homebrewnlp_tpu.analysis import ast_lint as ccd


def config_docs_complete_test():
    missing = ccd.missing_knobs()
    assert missing == [], (f"config knobs without a docs/CONFIG.md row: "
                           f"{missing}")


def lint_detects_missing_row_test(tmp_path):
    """The lint actually bites: a knob without a table row is reported, a
    documented one is not, derived state after the update loop is ignored."""
    cfg = tmp_path / "config.py"
    lines = ["class ModelParameter:",
             "    def __init__(self, config):",
             "        self._raw_config = dict(config)"]
    lines += [f"        self.knob_{i} = {i}" for i in range(60)]
    lines += ["        self.documented_knob = 1",
              "        self.forgotten_knob = 2",
              "        for k, v in config.items():",
              "            self.__dict__[k] = v",
              "        self.derived_state = self.documented_knob * 2"]
    cfg.write_text("\n".join(lines) + "\n")
    md = tmp_path / "CONFIG.md"
    md.write_text("| Key | Default |\n|---|---|\n"
                  + "".join(f"| `knob_{i}` | `{i}` |\n" for i in range(60))
                  + "| `documented_knob` | `1` |\n")
    missing = ccd.missing_knobs(str(cfg), str(md))
    assert missing == ["forgotten_knob"]
