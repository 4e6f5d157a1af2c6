"""graft-lint tier-1 suite: the static-analysis layer audits every jitted
entry point AND every pass/rule is proven to bite on a seeded violation.

Two positive checks pin the repo at HEAD clean (the compiled-HLO audit of
all four entry points against analysis/budgets.json, and the AST rules
over homebrewnlp_tpu/ + scripts/); each HLO pass and each AST rule then
gets a negative control — synthetic HLO text or source carrying exactly
the violation the pass exists to catch, mirroring the decode checker's
negative control (tests/decode_inplace_test.py) so no future refactor can
reduce an audit to a vacuous assertion.  The donation audit additionally
gets REAL negative controls: the train step and the prefill entry compiled
with donation disabled (the same jit, ``donate=False``) must be flagged.
"""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

from homebrewnlp_tpu.analysis import ast_lint, entry_points, hlo_lint

pytestmark = pytest.mark.staticanalysis

REPO = os.path.join(os.path.dirname(__file__), "..")


# ---- shared lowering (one audit model for the whole module) ----------------

@pytest.fixture(scope="module")
def audit_model():
    return entry_points.build_audit_model()


# ---- positive: the repo at HEAD is clean -----------------------------------

def hlo_audit_all_entry_points_clean_test():
    """All four jitted entry points (train step, decode chunk step, prefill
    entry, eval fn) pass every HLO pass against analysis/budgets.json."""
    findings = entry_points.audit_all()
    assert findings == [], "\n".join(str(f) for f in findings)


def ast_rules_repo_clean_test():
    findings = ast_lint.lint_repo()
    assert findings == [], "\n".join(str(f) for f in findings)


def budgets_cover_every_entry_point_test():
    """EXACTLY the registered entry points — an orphan row (entry renamed
    or dropped) would silently audit nothing, so it fails here and in
    ``mesh_audit.budget_coverage_audit`` (tests/mesh_audit_test.py covers
    the meshes-section half)."""
    budgets = hlo_lint.load_budgets()
    assert set(entry_points.ENTRY_POINTS) == set(budgets["entry_points"])


# ---- donation audit: real negative controls --------------------------------

def donation_audit_flags_undonated_train_step_test(audit_model):
    """The SAME train step compiled without donation must be flagged
    against the donated-case expectation — proof the audit reads the real
    alias table, not a vacuous count."""
    import jax

    params, model, variables, token_x, batch = audit_model
    trainer, state = entry_points.make_trainer(params, model, batch)
    hlo, ctx = entry_points.lower_train_step(params, model, variables,
                                             batch, donate=False,
                                             trainer=trainer, state=state)
    expected = len(jax.tree_util.tree_leaves(ctx["state"]))
    findings = hlo_lint.donation_audit("train_step", hlo, expected)
    assert findings and "NOT aliased" in findings[0].message
    # and the donated compile satisfies the same expectation
    hlo, ctx = entry_points.lower_train_step(params, model, variables,
                                             batch, donate=True,
                                             trainer=trainer, state=state)
    assert hlo_lint.donation_audit("train_step", hlo,
                                   ctx["donated_leaves"]) == []


def donation_audit_flags_undonated_prefill_entry_test(audit_model):
    import jax.numpy as jnp

    _, model, variables, token_x, _ = audit_model
    hlo, ctx = entry_points.lower_prefill_entry(model, variables,
                                                jnp.asarray(token_x),
                                                donate=False)
    findings = hlo_lint.donation_audit("prefill_entry_step", hlo,
                                       ctx["donated_leaves"])
    assert findings and findings[0].rule == "donation"


# ---- per-pass synthetic negative controls ----------------------------------

PROTECTED = {"f32[2,4,16,2,16]"}
LIVE_COPY = ("%copy.9 = f32[2,4,16,2,16]{4,3,2,1,0} "
             "copy(f32[2,4,16,2,16]{4,3,2,1,0} %get-tuple-element.1)")


def big_copy_audit_negative_control_test():
    findings = hlo_lint.big_copy_audit("e", LIVE_COPY, PROTECTED)
    assert findings and findings[0].rule == "big-copy"
    assert "NOT aliased" in findings[0].message


def big_copy_audit_async_pair_test():
    """Async copies count exactly once: ``copy-start``'s tuple result is
    unmatchable, its ``copy-done`` twin is flagged — at production scale
    XLA emits the big copies as async pairs, so this is where the round-5
    regression would actually surface on TPU."""
    pair = "\n".join([
        "%copy-start.9 = (f32[2,4,16,2,16]{4,3,2,1,0}, "
        "f32[2,4,16,2,16]{4,3,2,1,0}, u32[]{:S(2)}) "
        "copy-start(f32[2,4,16,2,16]{4,3,2,1,0} %get-tuple-element.1)",
        "%copy-done.9 = f32[2,4,16,2,16]{4,3,2,1,0} "
        "copy-done((f32[2,4,16,2,16]{4,3,2,1,0}, "
        "f32[2,4,16,2,16]{4,3,2,1,0}, u32[]{:S(2)}) %copy-start.9)",
    ])
    findings = hlo_lint.big_copy_audit("e", pair, PROTECTED)
    assert findings and findings[0].rule == "big-copy"
    nbytes = hlo_lint.shape_bytes("f32[2,4,16,2,16]")
    assert f"{nbytes} bytes copied" in findings[0].message  # counted ONCE


def big_copy_audit_relayout_of_live_state_test():
    """A relayout copy of FULL protected LIVE state (get-tuple-element
    operand — the carry) is the unaliasable-layout failure the
    pre-refactor decode checker named — still flagged."""
    relayout = ("%copy.2 = f32[2,4,16,2,16]{4,3,2,1,0} "
                "copy(f32[2,4,16,2,16]{0,1,2,3,4} %get-tuple-element.7)")
    findings = hlo_lint.big_copy_audit("e", relayout, PROTECTED)
    assert findings and findings[0].rule == "big-copy"


def big_copy_audit_exemptions_test():
    """The three legitimate copy flavors pass: differently-shaped buffers,
    fresh-init (broadcast operand) materialization, and relayout copies of
    explicit data-movement results (the train optimizer's transposes) —
    and a byte budget tolerates small preserved leaves."""
    block = ("%copy.1 = f32[4,16,2,16]{3,2,1,0} "
             "copy(f32[4,16,2,16]{2,0,3,1} %transpose.1)")
    fresh = ("%copy.3 = f32[2,4,16,2,16]{4,3,2,1,0} "
             "copy(f32[2,4,16,2,16]{4,3,2,1,0} %broadcast.2)")
    relayout_intermediate = ("%copy.4 = f32[2,4,16,2,16]{4,3,2,1,0} "
                             "copy(f32[2,4,16,2,16]{0,1,2,3,4} "
                             "%transpose.9)")
    for ok in (block, fresh, relayout_intermediate):
        assert hlo_lint.big_copy_audit("e", ok, PROTECTED) == [], ok
    # a budget at least the copied bytes tolerates the copy...
    nbytes = hlo_lint.shape_bytes("f32[2,4,16,2,16]")
    assert hlo_lint.big_copy_audit("e", LIVE_COPY, PROTECTED,
                                   max_copied_bytes=nbytes) == []
    # ...one byte less does not
    assert hlo_lint.big_copy_audit("e", LIVE_COPY, PROTECTED,
                                   max_copied_bytes=nbytes - 1)


def dtype_promotion_audit_negative_control_test():
    bad = "%convert.5 = f32[32,64]{1,0} convert(bf16[32,64]{1,0} %p.7)"
    params = {"bf16[32,64]"}
    findings = hlo_lint.dtype_promotion_audit("e", bad, params)
    assert findings and findings[0].rule == "dtype-promotion"
    # allowlisted shape passes; a non-param shape was never in scope
    assert hlo_lint.dtype_promotion_audit("e", bad, params,
                                          allow={"bf16[32,64]"}) == []
    other = "%convert.5 = f32[8,8]{1,0} convert(bf16[8,8]{1,0} %p.7)"
    assert hlo_lint.dtype_promotion_audit("e", other, params) == []


def collective_census_and_budget_negative_control_test():
    hlo = "\n".join([
        "%all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %x)",
        # async pair: -start counts, -done must not double-count
        "%ag = (f32[4]{0}, f32[8]{0}) all-gather-start(f32[4]{0} %y)",
        "%ag2 = f32[8]{0} all-gather-done((f32[4]{0}, f32[8]{0}) %ag)",
    ])
    census = hlo_lint.collective_census(hlo)
    assert census["all-reduce"] == 1 and census["all-gather"] == 1
    assert census["reduce-scatter"] == 0
    findings = hlo_lint.collective_budget_audit("e", census, {})
    assert {f.rule for f in findings} == {"collective-budget"}
    assert len(findings) == 2  # one per over-budget op kind
    assert hlo_lint.collective_budget_audit(
        "e", census, {"all-reduce": 1, "all-gather": 1}) == []


def host_sync_audit_negative_control_test():
    infeed = "%infeed.1 = (f32[4]{0}, token[]) infeed(token[] %tok)"
    cb = ('%custom-call.2 = f32[4]{0} custom-call(f32[4]{0} %x), '
          'custom_call_target="xla_python_cpu_callback"')
    for bad in (infeed, cb):
        findings = hlo_lint.host_sync_audit("e", bad)
        assert findings and findings[0].rule == "host-sync", bad
    clean = "%add.1 = f32[4]{0} add(f32[4]{0} %x, f32[4]{0} %y)"
    assert hlo_lint.host_sync_audit("e", clean) == []


# ---- AST rules: seeded-violation negative controls -------------------------

def wallclock_rule_negative_control_test():
    bad = "import time\nt0 = time.time()\n"
    findings = ast_lint.lint_source("x.py", bad)
    assert [f.rule for f in findings] == ["wallclock"]
    assert findings[0].entry == "x.py:2"
    ok = "import time\nt0 = time.monotonic()\n"
    assert ast_lint.lint_source("x.py", ok) == []


def wallclock_rule_alias_spellings_test():
    """Every spelling of the wall clock is caught — a from-import or module
    alias must not bypass the ban."""
    for bad in ("from time import time\nt0 = time()\n",
                "from time import time as now\nt0 = now()\n",
                "import time as t\nt0 = t.time()\n"):
        assert [f.rule for f in ast_lint.lint_source("x.py", bad)] \
            == ["wallclock"], bad
    # other names stay out of scope: monotonic from-imports, local time()
    for ok in ("from time import monotonic\nt0 = monotonic()\n",
               "def time():\n    return 0\nt0 = time()\n"):
        assert ast_lint.lint_source("x.py", ok) == [], ok


def wallclock_rule_suppression_test():
    marked = ("import time\n"
              "stamp = time.time()  # graft-lint: allow[wallclock]\n")
    assert ast_lint.lint_source("x.py", marked) == []
    line_above = ("import time\n"
                  "# graft-lint: allow[wallclock]\n"
                  "stamp = time.time()\n")
    assert ast_lint.lint_source("x.py", line_above) == []
    # the marker is rule-scoped: it does not blanket other rules
    wrong_rule = ("import time\n"
                  "t = time.time()  # graft-lint: allow[unseeded-rng]\n")
    assert [f.rule for f in ast_lint.lint_source("x.py", wrong_rule)] \
        == ["wallclock"]


def unseeded_rng_rule_negative_control_test():
    bad = "import numpy as np\nr = np.random.default_rng()\n"
    findings = ast_lint.lint_source("x.py", bad)
    assert [f.rule for f in findings] == ["unseeded-rng"]
    assert ast_lint.lint_source(
        "x.py", "import numpy as np\nr = np.random.default_rng(7)\n") == []
    marked = ("import numpy as np\n"
              "r = np.random.default_rng()  # graft-lint: allow[unseeded-rng]\n")
    assert ast_lint.lint_source("x.py", marked) == []


def donated_jit_rule_negative_control_test():
    bad = ("import jax\n"
           "def my_new_step():\n"
           "    return jax.jit(lambda x: x, donate_argnums=(0,))\n")
    findings = ast_lint.lint_source("some/new_module.py", bad)
    assert [f.rule for f in findings] == ["donated-jit"]
    assert "some/new_module.py::my_new_step" in findings[0].message
    # the registered real site passes under its registry key
    registered = ("import jax\n"
                  "def _build_step():\n"
                  "    return jax.jit(lambda x: x, donate_argnums=(0,))\n")
    assert ast_lint.lint_source(
        "homebrewnlp_tpu/train/__init__.py", registered) == []
    # a jit WITHOUT donation needs no registration
    plain = "import jax\nf = jax.jit(lambda x: x)\n"
    assert ast_lint.lint_source("some/new_module.py", plain) == []


def engine_registry_rule_negative_control_test():
    """A donated jit under infer/ outside the Engine's registered builder
    sites is a forked chunk-program carry escaping the composition
    registry: the engine-registry rule flags it (on top of donated-jit);
    the registered builder stays clean under its key, and the same site
    OUTSIDE infer/ trips only the donated-jit registration rule."""
    bad = ("import jax\n"
           "def my_forked_program():\n"
           "    return jax.jit(lambda c: c, donate_argnums=(0,))\n")
    findings = ast_lint.lint_source("homebrewnlp_tpu/infer/forked.py", bad)
    assert sorted(f.rule for f in findings) == ["donated-jit",
                                               "engine-registry"]
    msg = next(f.message for f in findings if f.rule == "engine-registry")
    assert "ENGINE_PROGRAMS" in msg and "_chunk_jit" in msg
    # the Engine's single builder passes under its registered key
    registered = ("import jax\n"
                  "def _chunk_jit():\n"
                  "    return jax.jit(lambda c: c, donate_argnums=(0,))\n")
    assert ast_lint.lint_source("homebrewnlp_tpu/infer/engine.py",
                                registered) == []
    # outside infer/ the composition registry does not apply
    assert [f.rule for f in ast_lint.lint_source(
        "homebrewnlp_tpu/train/other.py", bad)] == ["donated-jit"]
    # the suppression marker silences the fork complaint too
    marked = ("import jax\n"
              "def my_forked():  # graft-lint: allow[engine-registry]\n"
              "    return jax.jit(lambda c: c, donate_argnums=(0,))  "
              "# graft-lint: allow[donated-jit]\n")
    assert ast_lint.lint_source("homebrewnlp_tpu/infer/forked.py",
                                marked) == []


def registry_keys_point_at_real_sites_test():
    """Every DONATED_JIT_REGISTRY / ENGINE_REGISTRY_SITES key names an
    existing file — a stale key after a refactor would silently stop
    covering (or stop permitting) the moved site."""
    for key in (set(ast_lint.DONATED_JIT_REGISTRY)
                | set(ast_lint.ENGINE_REGISTRY_SITES)):
        rel = key.split("::")[0]
        assert os.path.exists(os.path.join(REPO, rel)), key
    # the Engine builder's registry row promises an audit per composition
    assert ("homebrewnlp_tpu/infer/engine.py::_chunk_jit"
            in ast_lint.ENGINE_REGISTRY_SITES)


def engine_programs_mirror_entry_points_test():
    """infer/engine.py ENGINE_PROGRAMS and analysis/entry_points.py
    ENTRY_POINTS are mirrored, not imported (entry_points must import
    without jax): the chunk-step tail of the audit registry must list
    exactly the Engine's compositions in registry order, every
    (spec, paged) pair must resolve to exactly one program, and the
    builder's DONATED_JIT_REGISTRY row must name each audit."""
    from homebrewnlp_tpu.analysis import entry_points
    from homebrewnlp_tpu.infer.engine import ENGINE_PROGRAMS, program_name
    progs = list(ENGINE_PROGRAMS)
    assert list(entry_points.ENTRY_POINTS[-len(progs):]) == progs
    assert sorted(program_name(**parts)
                  for parts in ENGINE_PROGRAMS.values()) == sorted(progs)
    row = ast_lint.DONATED_JIT_REGISTRY[
        "homebrewnlp_tpu/infer/engine.py::_chunk_jit"]
    for name in progs:
        assert name in row, (name, row)


def env_knob_rule_clean_tree_test():
    """No environment read in the layers that build the step program but
    the allowlisted ones — and every allowlisted name is still read there
    (an entry whose debt was paid must leave the list)."""
    read = set()
    for path, rel in ast_lint.iter_source_files():
        if not rel.startswith(ast_lint.ENV_KNOB_DIRS):
            continue
        with open(path) as f:
            src = f.read()
        found = [f_ for f_ in ast_lint.lint_source(rel, src)
                 if f_.rule == "env-knob"]
        assert found == [], "\n".join(str(f_) for f_ in found)
        read |= {k for k in ast_lint.ENV_KNOB_ALLOWED if f'"{k}"' in src}
    assert read == set(ast_lint.ENV_KNOB_ALLOWED)


def env_knob_rule_negative_control_test():
    rel = "homebrewnlp_tpu/parallel/new_kernel.py"
    for bad in ('import os\nx = os.environ.get("HBNLP_NEW_TILE")\n',
                'import os\nx = os.getenv("HBNLP_NEW_TILE", "1")\n',
                'import os\nx = os.environ["HBNLP_NEW_TILE"]\n',
                'from os import environ\nx = environ.get("HBNLP_NEW_TILE")\n',
                'import os\nk = "A" + "B"\nx = os.environ.get(k)\n',
                'import os\nif "HBNLP_NEW_PATH" in os.environ:\n    x = 1\n',
                'import os\nx = "HBNLP_NEW_PATH" not in os.environ\n',
                'import os\nx = os.environ.setdefault("HBNLP_NEW_TILE", "8")\n',
                'import os\nx = os.environ.pop("HBNLP_NEW_TILE", None)\n'):
        assert [f.rule for f in ast_lint.lint_source(rel, bad)] \
            == ["env-knob"], bad
    allowed = 'import os\nx = os.environ.get("HBNLP_MAP_MIXER_INTERPRET")\n'
    assert ast_lint.lint_source(rel, allowed) == []
    # the other layers keep their deployment settings (addresses, paths)
    assert ast_lint.lint_source("homebrewnlp_tpu/distributed/x.py",
                                'import os\nx = os.environ["HOST"]\n') == []


def layering_rule_clean_tree_test():
    """Imports point downwards in the whole tree; the declaration readers
    name no layer; and every excepted module still imports upwards (an entry
    whose debt was paid must leave the list)."""
    upward = set()
    for path, rel in ast_lint.iter_source_files():
        with open(path) as f:
            src = f.read()
        found = [f_ for f_ in ast_lint.lint_source(rel, src)
                 if f_.rule == "layering"]
        assert found == [], "\n".join(str(f_) for f_ in found)
        if rel in ast_lint.LAYERING_EXCEPTIONS and "from ..model" in src:
            upward.add(rel)
    assert upward == set(ast_lint.LAYERING_EXCEPTIONS)


@pytest.mark.parametrize("rel,source,findings", [
    # a lower layer reaching up, in every spelling
    ("homebrewnlp_tpu/parallel/new_kernel.py",
     "from ..model.blocks import stash_push\n", 1),
    ("homebrewnlp_tpu/core/quant.py",
     "def f():\n    from ..model.backend import _BLOCK_RE\n", 1),
    ("homebrewnlp_tpu/core/x.py", "from .. import model\n", 1),
    ("homebrewnlp_tpu/telemetry/x.py",
     "import homebrewnlp_tpu.train as t\n", 1),
    ("homebrewnlp_tpu/optim/x.py", "from ..run.train_loop import train\n", 1),
    ("homebrewnlp_tpu/parallel/x.py", "from ..infer import engine\n", 1),
    # downwards and sideways are fine, and so are the named exceptions
    ("homebrewnlp_tpu/parallel/new_kernel.py",
     "from ..core.stash import stash_push\nfrom .flash_attention import "
     "kernel_block\nfrom ..config import ModelParameter\n", 0),
    ("homebrewnlp_tpu/parallel/pipeline.py",
     "from ..model.blocks import rev_sequence\n", 0),
    ("homebrewnlp_tpu/model/spatial.py",
     "from ..parallel.flash_attention import attention\n", 0),
    # the declaration readers name no layer and no kernel
    ("homebrewnlp_tpu/model/remat.py",
     "from .moe import SAVED_NAMES\nfrom . import cca\n", 2),
    ("homebrewnlp_tpu/model/remat.py",
     "def f():\n    from ..parallel.flash_attention import band_applies\n",
     1),
    ("homebrewnlp_tpu/train/__init__.py",
     "from ..model.spatial import _DENSE_ONLY\nfrom ..parallel.causal_conv "
     "import kernel_applies\n", 2),
    ("homebrewnlp_tpu/train/__init__.py",
     "from ..model import Model, declare\nfrom ..model.remat import "
     "stash_plan\nfrom ..core import sharding\n", 0),
    ("homebrewnlp_tpu/model/remat.py",
     "from .declare import offers\nfrom ..utils.flops import peak_flops\n",
     0)])
def layering_rule_negative_control_test(rel, source, findings):
    assert [f.rule for f in ast_lint.lint_source(rel, source)] \
        == ["layering"] * findings, source


def config_docs_rule_negative_control_test(tmp_path):
    cfg = tmp_path / "config.py"
    lines = ["class ModelParameter:",
             "    def __init__(self, config):"]
    lines += [f"        self.knob_{i} = {i}" for i in range(60)]
    lines += ["        self.forgotten_knob = 2",
              "        for k, v in config.items():",
              "            self.__dict__[k] = v"]
    cfg.write_text("\n".join(lines) + "\n")
    md = tmp_path / "CONFIG.md"
    md.write_text("| Key | Default |\n|---|---|\n"
                  + "".join(f"| `knob_{i}` | `{i}` |\n" for i in range(60)))
    findings = ast_lint.config_docs_findings(str(cfg), str(md))
    assert [f.rule for f in findings] == ["config-docs"]
    assert "forgotten_knob" in findings[0].message


# ---- the CLI ---------------------------------------------------------------

def graft_lint_cli_ast_clean_test():
    """`graft_lint.py --ast` exits 0 on the repo at HEAD (the full --all
    run rides the in-process audit_all test above; the subprocess here pins
    argument parsing + exit semantics without a second 15 s compile)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "graft_lint.py"),
         "--ast"], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def graft_lint_cli_reports_findings_test(monkeypatch):
    """Findings drive a nonzero exit and a per-rule summary on stderr."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import graft_lint
    finally:
        sys.path.pop(0)
    fake = [hlo_lint.Finding("donation", "train_step", "seeded"),
            hlo_lint.Finding("donation", "eval_fn", "seeded"),
            hlo_lint.Finding("big-copy", "train_step", "seeded")]
    monkeypatch.setattr(graft_lint, "run_ast", lambda: list(fake))
    assert graft_lint.main(["--ast"]) == 1
    monkeypatch.setattr(graft_lint, "run_ast", lambda: [])
    assert graft_lint.main(["--ast"]) == 0


def int8_promotion_audit_negative_control_test():
    """A synthetic dequant-scope-less int8 promotion IS flagged (the pass
    has teeth), while the same line under a dequant scope is not."""
    bad = ('  %evil = f32[4,256,128]{2,1,0} convert(s8[4,256,128]{2,1,0} '
           '%w), metadata={op_name="jit(step_fn)/gpt0/body0/somewhere/'
           'convert_element_type"}')
    good = bad.replace("body0/somewhere", "body0/dequant")
    assert hlo_lint.int8_promotion_audit("t", bad)
    assert not hlo_lint.int8_promotion_audit("t", good)
