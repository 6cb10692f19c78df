"""tracelint: static trace-safety analyzer + registry auditor.

Covers: the rule framework (ids, severities, suppression), every rule
against a seeded-hazard corpus (each rule must fire exactly where
expected), the zero-error guarantee on the clean model-zoo corpus, the
live registry audit, `to_static(check=True)` integration (warnings
surface, semantics unchanged), the dispatch.override near-miss error,
the shard_map compat helper, and the CLI/tier-1 `--self` wiring.
"""
import ast
import inspect
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import analysis
from paddle_tpu.analysis import core as acore
from paddle_tpu.analysis import registry_audit as raudit
from paddle_tpu.analysis.taint import TENSOR, SHAPE, UNTAINTED

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def lint(src):
    return analysis.lint_source(src, "<test>")


def rules_fired(src):
    return {f.rule for f in lint(src)}


# ===================================================================
# framework
# ===================================================================
def test_registry_has_at_least_ten_distinct_rules():
    rules = analysis.all_rules()
    assert len(rules) >= 10
    assert len({r.id for r in rules.values()}) == len(rules)
    for r in rules.values():
        assert r.severity in analysis.SEVERITIES
        assert r.id.startswith("TL")
        # a rule participates either via visitor interests or by owning
        # its own descent in finish() (e.g. TL013 walks host loops)
        assert (r.interests
                or type(r).finish is not analysis.Rule.finish), \
            f"{r.id} declares no visitor interests and no finish()"


def test_finding_shape_and_sorting():
    fs = lint("def forward(x):\n y = x.numpy()\n t = x.item()\n return t\n")
    assert [f.line for f in fs] == sorted(f.line for f in fs)
    d = fs[0].as_dict()
    assert {"file", "line", "col", "rule", "severity", "message",
            "hint", "func"} <= set(d)
    assert fs[0].func == "forward"
    assert "<test>" in fs[0].render()


def test_suppression_comment_by_id_and_blanket():
    src = ("def forward(x):\n"
           "    a = x.numpy()  # tracelint: disable=TL001\n"
           "    b = x.item()  # tracelint: disable\n"
           "    c = x.tolist()  # tracelint: disable=TL999\n"
           "    return a, b, c\n")
    fs = lint(src)
    assert [f.line for f in fs] == [4]   # only the wrong-id suppression


def test_syntax_error_is_reported_not_raised():
    fs = analysis.lint_source("def broken(:\n", "bad.py")
    assert len(fs) == 1 and fs[0].rule == "TL999"


# ===================================================================
# seeded-hazard corpus: each rule fires exactly where expected
# ===================================================================
HAZARDS = {
    "TL001": "def forward(x):\n    v = x.numpy()\n    return v\n",
    "TL002": "def forward(x):\n    return float(x.sum())\n",
    "TL003": ("import time\n"
              "def forward(x):\n    t = time.time()\n    return x * t\n"),
    "TL004": ("import numpy as np\n"
              "def forward(x):\n"
              "    return x + np.random.randn(4)\n"),
    "TL005": "def forward(x):\n    print(x)\n    return x\n",
    "TL006": ("def forward(x):\n"
              "    global STEP\n    STEP = STEP + 1\n    return x\n"),
    "TL007": ("def forward(x):\n"
              "    if x.sum() > 0:\n        return x\n"
              "    return x * 2\n"),
    "TL008": None,   # needs live closure inspection — tested separately
    "TL009": ("def forward(x,\n"
              "            scales=[1.0, 2.0]):\n"
              "    return x * scales[0]\n"),
    "TL010": ("def forward(x):\n"
              "    if x.shape[0] > 128:\n        x = x * 2\n"
              "    return x\n"),
    "TL011": ("def forward(self, x):\n"
              "    if x.mean() > 0:\n        self.cache[0] = x\n"
              "    return x\n"),
    "TL012": "def forward(x):\n    assert x.min() > 0\n    return x\n",
}


@pytest.mark.parametrize("rule_id", sorted(k for k, v in HAZARDS.items()
                                           if v is not None))
def test_each_rule_fires_on_its_seeded_hazard(rule_id):
    fs = [f for f in lint(HAZARDS[rule_id]) if f.rule == rule_id]
    assert fs, f"{rule_id} did not fire on its hazard fixture"
    # and the finding anchors to the hazardous statement, not line 1
    assert all(f.line > 1 for f in fs)


def test_seeded_hazards_fire_only_their_own_rule():
    # fixtures are minimal: no fixture may trip an unrelated ERROR rule
    for rule_id, src in HAZARDS.items():
        if src is None:
            continue
        extra = {f.rule for f in lint(src)
                 if f.severity == "error"} - {rule_id}
        assert not extra, f"{rule_id} fixture also fired {extra}"


def test_tl001_variants_and_host_path_silence():
    assert "TL001" in rules_fired(
        "def forward(x):\n    return x.tolist()\n")
    # a host-side helper (not trace-path) stays silent
    assert rules_fired(
        "def load(path):\n    return path.numpy()\n") == set()


def test_tl007_every_path_returns_form_is_allowed():
    src = ("def forward(x):\n"
           "    if x.sum() > 0:\n        return x\n"
           "    else:\n        return x * 2\n")
    assert "TL007" not in rules_fired(src)


def test_tl007_break_under_tensor_if():
    src = ("def forward(x):\n"
           "    for i in range(3):\n"
           "        if x.sum() > 0:\n            break\n"
           "        x = x + 1\n"
           "    return x\n")
    assert "TL007" in rules_fired(src)


def test_tl010_static_python_branch_is_silent():
    src = ("def forward(x, training: bool):\n"
           "    if training:\n        x = x * 2\n"
           "    return x\n")
    assert "TL010" not in rules_fired(src)


def test_lint_function_line_numbers_survive_decorators():
    """Findings from a decorated function must point at the real file
    line — co_firstlineno is the first DECORATOR line, and the source
    snippet starts there too."""
    import functools

    def deco(f):
        @functools.wraps(f)
        def inner(*a):
            return f(*a)
        return inner

    @deco
    def forward(x):
        v = x.numpy()
        return v

    target = inspect.unwrap(forward)
    hazard_line = target.__code__.co_firstlineno + 2  # decorator, def, v=
    fs = [f for f in analysis.lint_function(forward) if f.rule == "TL001"]
    assert fs and fs[0].line == hazard_line, \
        (fs, hazard_line)


def test_hazards_inside_match_cases_are_seen():
    src = ("def forward(x, mode: str):\n"
           "    match mode:\n"
           "        case 'sync':\n"
           "            y = x.numpy()\n"
           "        case _:\n"
           "            y = x * 2\n"
           "    return y\n")
    fs = lint(src)
    assert "TL001" in {f.rule for f in fs}
    assert [f.line for f in fs if f.rule == "TL001"] == [4]


def test_functions_inside_try_handlers_are_discovered():
    src = ("try:\n"
           "    import fastpath\n"
           "except ImportError:\n"
           "    def forward(x):\n"
           "        return x.numpy()\n")
    assert "TL001" in {f.rule for f in lint(src)}


def test_tl008_closure_tensor_via_lint_function():
    w = pt.ones([2, 2])

    def forward(x):
        return x.matmul(w)

    fs = analysis.lint_function(forward)
    assert "TL008" in {f.rule for f in fs}

    def clean_fn(x):
        return x * 2

    assert "TL008" not in {f.rule for f in analysis.lint_function(clean_fn)}


def test_taint_is_flow_and_annotation_aware():
    src = ("def forward(x, axis: int, flag=True):\n"
           "    n = x.shape[0]\n"
           "    y = x * 2\n"
           "    z = len(x)\n"
           "    p = x is None\n"
           "    return y\n")
    tree = ast.parse(src)
    fctx = acore.FunctionContext(tree.body[0], "<t>", "forward",
                                 trace_path=True)
    from paddle_tpu.analysis.taint import TaintPass
    env = TaintPass(fctx).run()
    assert env["x"] == TENSOR and env["y"] == TENSOR
    assert env["n"] == SHAPE and env["z"] == SHAPE
    assert env["axis"] == UNTAINTED and env["flag"] == UNTAINTED
    assert env["p"] == UNTAINTED


# ===================================================================
# clean-corpus guarantee (model zoo) + baseline self-lint
# ===================================================================
CLEAN_TARGETS = ["paddle_tpu/vision/models", "paddle_tpu/text/bert.py",
                 "paddle_tpu/text/llama.py"]


def test_model_zoo_has_zero_error_findings():
    for target in CLEAN_TARGETS:
        fs = analysis.lint_path(os.path.join(REPO, target))
        errors = [f for f in fs if f.severity == "error"]
        assert not errors, f"{target}: {[f.render() for f in errors]}"


def test_self_lint_matches_checked_in_baseline():
    from paddle_tpu.analysis import cli
    baseline = cli.load_baseline(cli.default_baseline_path())
    assert baseline, "baseline file missing or empty"
    fresh = []
    for target in cli.self_lint_targets():
        for f in analysis.lint_path(target):
            if cli.finding_key(f, REPO) not in baseline:
                fresh.append(f)
    assert not fresh, [f.render() for f in fresh]


# ===================================================================
# registry audit
# ===================================================================
def test_live_registry_audit_is_clean():
    assert raudit.audit_registry() == []


def test_audit_flags_invalid_amp_and_bad_impl():
    from paddle_tpu.ops import dispatch
    dispatch._REGISTRY["_bad_tmp"] = dispatch.OpDef(
        "_bad_tmp", lambda x: x, "sometimes")
    try:
        ids = {f.rule for f in raudit.audit_live_registry()}
        assert "REG001" in ids
    finally:
        del dispatch._REGISTRY["_bad_tmp"]


def test_audit_flags_incompatible_override_signature():
    from paddle_tpu.ops import dispatch
    dispatch.register("_sig_tmp", lambda x, alpha=1.0: x * alpha)
    try:
        dispatch.override("_sig_tmp", lambda x, *, beta: x * beta)
        ids = {f.rule for f in raudit.audit_live_registry()}
        assert "REG004" in ids
    finally:
        del dispatch._REGISTRY["_sig_tmp"]
        dispatch._OVERRIDDEN.discard("_sig_tmp")


def test_audit_source_flags_duplicate_register(tmp_path):
    pkg = tmp_path / "fake_ops"
    pkg.mkdir()
    (pkg / "a.py").write_text(
        "register('dup', lambda x: x)\n"
        "register('dup', lambda x: x * 2)\n"
        "override('missing', lambda x: x)\n"
        "register('badamp', lambda x: x, amp='fp42')\n")
    ids = {f.rule for f in raudit.audit_ops_source(str(pkg))}
    assert {"REG002", "REG003", "REG001"} <= ids


# ===================================================================
# integration: to_static(check=True) + env var + recompile cross-ref
# ===================================================================
def test_to_static_check_true_warns_and_preserves_semantics():
    class Net(pt.nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = pt.nn.Linear(4, 4)

        def forward(self, x):
            print("tracing")
            return self.fc(x)

    net = Net()
    x = pt.randn([2, 4])
    ref = net(x)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        st = pt.jit.to_static(net, check=True)
    assert any(issubclass(i.category, analysis.TraceLintWarning) and
               "TL005" in str(i.message) for i in w)
    np.testing.assert_allclose(np.asarray(st(x)._array),
                               np.asarray(ref._array), rtol=1e-6)


def test_to_static_check_env_var(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TRACELINT", "1")

    @pt.jit.not_to_static
    def f(x):
        t = x.item()
        return x

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        pt.jit.to_static(f)
    assert any("TL001" in str(i.message) for i in w)


def test_check_false_stays_silent():
    def f(x):
        return x.item()

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        pt.jit.to_static(f)
    assert not [i for i in w
                if issubclass(i.category, analysis.TraceLintWarning)]


def test_recompile_warning_names_static_rule():
    from paddle_tpu.observability import compile_tracker as ct
    assert analysis.static_rule_for_cause("shape change") == "TL010"
    assert analysis.static_rule_for_cause("new static arg") == "TL009"
    assert "TL010" in ct._static_rule_hint("shape change")
    assert ct._static_rule_hint("dtype change") == ""


# ===================================================================
# satellites: override near-miss, shard_map compat
# ===================================================================
def test_override_unknown_op_lists_near_misses():
    from paddle_tpu.ops import dispatch
    with pytest.raises(KeyError) as ei:
        dispatch.override("matmull", lambda a, b: a @ b)
    msg = str(ei.value)
    assert "matmull" in msg and "matmul" in msg and "registered" in msg


def test_shard_map_compat_resolves_and_runs():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.framework import compat
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >=2 devices")
    mesh = Mesh(np.array(devs[:2]), ("x",))
    f = compat.shard_map(lambda a: a * compat.axis_size("x"),
                         mesh, in_specs=P("x"), out_specs=P("x"),
                         check_vma=False)
    out = jax.jit(f)(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0) * 2)


def test_shard_map_compat_partial_manual_contract():
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.framework import compat
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs >=4 devices")
    import jax.numpy as jnp
    mesh = Mesh(np.array(devs[:4]).reshape(2, 2), ("pp", "dp"))
    # manual over pp only, GSPMD keeps dp: each pp shard sees its half
    f = compat.shard_map(lambda a: a + compat.axis_index("pp"), mesh,
                         in_specs=P("pp"), out_specs=P("pp"),
                         axis_names={"pp"})
    out = jax.jit(f)(jnp.zeros((4, 2)))
    np.testing.assert_allclose(np.asarray(out)[:, 0], [0, 0, 1, 1])


# ===================================================================
# CLI + tier-1 --self wiring
# ===================================================================
def _run_cli(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tracelint.py"),
         *args], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


@pytest.mark.slow
def test_cli_json_output(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def forward(x):\n    return x.numpy()\n")
    r = _run_cli("--json", str(bad))
    assert r.returncode == 1
    data = json.loads(r.stdout)
    assert data and data[0]["rule"] == "TL001"


def test_cli_self_inprocess():
    """The tier-1 wiring: registry audit + self-lint vs baseline must be
    green in-process (mirrors tools/trace_check.py in PR 2)."""
    import io
    from paddle_tpu.analysis import cli
    buf = io.StringIO()
    assert cli.run_self(out=buf) == 0, buf.getvalue()
    assert "registry audit OK" in buf.getvalue()


@pytest.mark.slow
def test_cli_self_subprocess():
    r = _run_cli("--self")
    assert r.returncode == 0, r.stdout + r.stderr


# ===================================================================
# TL013: loop-variant shapes in HOST decode/step loops (PR 7)
# ===================================================================
def test_tl013_fires_on_host_decode_loop_constructors():
    # constructor function-form: shape arg is args[0]
    src = ("import jax.numpy as jnp\n"
           "def decode(model, ids, b, d, max_new):\n"
           "    for t in range(max_new):\n"
           "        k = jnp.zeros((b, t + 1, d))\n"
           "        ids = model(ids, k)\n"
           "    return ids\n")
    assert "TL013" in rules_fired(src)
    # data-first function-form: the shape arg is the SECOND positional
    for call in ("jnp.broadcast_to(x, (b, t + 1, d))",
                 "jnp.tile(x, (1, t + 1))",
                 "jnp.pad(x, ((0, t), (0, 0)))",
                 "jnp.reshape(x, (b, t + 1))"):
        src = ("import jax.numpy as jnp\n"
               "def decode(x, b, d, max_new):\n"
               "    for t in range(max_new):\n"
               f"        x2 = {call}\n"
               "    return x2\n")
        assert "TL013" in rules_fired(src), call
    # method form: every positional arg is shape-ish
    src = ("def step(x, b, max_new):\n"
           "    for t in range(max_new):\n"
           "        y = x.reshape(b, t + 1)\n"
           "    return y\n")
    assert "TL013" in rules_fired(src)


def test_tl013_silent_on_safe_loops():
    # loop-invariant shapes: no storm
    src = ("import jax.numpy as jnp\n"
           "def decode(x, b, d, max_new):\n"
           "    for t in range(max_new):\n"
           "        k = jnp.zeros((b, 64, d))\n"
           "    return k\n")
    assert "TL013" not in rules_fired(src)
    # data-first function form with a loop-variant DATA arg only: the
    # output shape follows the pad widths, not the array argument
    src = ("import jax.numpy as jnp\n"
           "def decode(xs, max_new):\n"
           "    for t in range(max_new):\n"
           "        y = jnp.pad(xs[t], ((0, 4), (0, 0)))\n"
           "    return y\n")
    assert "TL013" not in rules_fired(src)
    # a loop INSIDE a trace-path function unrolls into one program
    src = ("import jax.numpy as jnp\n"
           "def forward(x, b):\n"
           "    for t in range(4):\n"
           "        x = x + jnp.zeros((b, t + 1))\n"
           "    return x\n")
    assert "TL013" not in rules_fired(src)
