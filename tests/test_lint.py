"""mxlint: the consolidated static-analysis gate (tier-1) plus tests of
the framework itself — fixtures per rule, pragma suppression, baseline
freezing, knob-table/README sync, the single-parse-pass guarantee, the
PR-6 interprocedural engine (call graph, reason chains, hot-path
roots), ``--fix`` round-trips, and the two-pass perf budget.

The whole suite shares ONE memoized repo lint (``mxlint.check_repo``);
the thin per-rule assertions that replaced the old copy-pasted AST
walkers in test_resilience / test_engine_bulk / test_observability
reuse the same run."""
import ast
import os
import time

import pytest

from mxnet_tpu.tools import mxlint
from mxnet_tpu.tools.mxlint import core as mxcore
from mxnet_tpu.tools.mxlint import fix as mxfix
from mxnet_tpu.tools.mxlint import graph as mxgraph
from mxnet_tpu.tools.mxlint import rules as mxrules

REPO = mxlint.REPO_ROOT
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lint_fixtures")

RULE_FOR_FIXTURE = {
    "bare_except": "bare-except",
    "lru": "unbounded-lru-method",
    "counter_dict": "counter-dict",
    "timing_pair": "timing-pair",
    "lock_discipline": "lock-discipline",
    "lock_order": "lock-discipline",
    "lock_reacquire": "lock-discipline",
    "collective_safety": "collective-safety",
    "collective_transitive": "collective-safety",
    "collective_membership": "collective-safety",
    "collective_reduce_scatter": "collective-safety",
    "hot_path_purity": "hot-path-purity",
    "hidden_host_sync": "hidden-host-sync",
    "env_knob": "env-knob",
    "env_knob_write": "env-knob",
    # PR-20: the flow-sensitive (CFG) tier
    "resource_leak": "resource-leak",
    "thread_lifecycle": "thread-lifecycle",
    "blocking_under_lock": "blocking-under-lock",
}


def _fixture(name: str) -> str:
    path = os.path.join(FIXTURES, name)
    with open(path, "r", encoding="utf-8") as f:
        return f.read()


# -- THE gate: the tree is clean against the frozen baseline ----------------

def test_package_tree_is_clean():
    """Tier-1 acceptance: ``python -m mxnet_tpu.tools.mxlint`` exits 0
    on this tree — zero new findings across all twelve rules."""
    new, _baselined = mxlint.check_repo()
    assert new == [], "new mxlint findings:\n" + \
        "\n".join(repr(f) for f in new)


def test_all_rules_registered():
    assert set(mxlint.ALL_RULES) == set(RULE_FOR_FIXTURE.values())
    assert len(mxlint.ALL_RULES) == 12


# -- per-rule fixtures: positive must trip, negative must pass --------------

@pytest.mark.parametrize("stem", sorted(RULE_FOR_FIXTURE))
def test_rule_trips_on_bad_fixture(stem):
    rule = RULE_FOR_FIXTURE[stem]
    new, _sup = mxlint.lint_source(
        _fixture(f"{stem}_bad.py"),
        relpath=f"tests/lint_fixtures/{stem}_bad.py")
    assert new, f"{rule} did not trip on its positive fixture"
    # purity: a fixture exercises exactly its own rule
    assert {f.rule for f in new} == {rule}, new


@pytest.mark.parametrize("stem", sorted(RULE_FOR_FIXTURE))
def test_rule_passes_on_ok_fixture(stem):
    new, _sup = mxlint.lint_source(
        _fixture(f"{stem}_ok.py"),
        relpath=f"tests/lint_fixtures/{stem}_ok.py")
    assert new == [], new


def test_cli_exits_nonzero_on_each_bad_fixture(capsys):
    """Acceptance: the CLI exits nonzero on every rule's positive
    fixture (run in-process — same code path as ``python -m``)."""
    for stem in RULE_FOR_FIXTURE:
        rc = mxlint.main([os.path.join(FIXTURES, f"{stem}_bad.py")])
        assert rc != 0, f"CLI exited 0 on {stem}_bad.py"
        rc = mxlint.main([os.path.join(FIXTURES, f"{stem}_ok.py")])
        assert rc == 0, f"CLI exited nonzero on {stem}_ok.py"
    capsys.readouterr()


def test_cli_json_output(capsys):
    import json as _json
    rc = mxlint.main(["--json",
                      os.path.join(FIXTURES, "bare_except_bad.py")])
    out = capsys.readouterr().out
    assert rc == 1
    payload = _json.loads(out)
    assert payload["new"] and \
        payload["new"][0]["rule"] == "bare-except"
    assert "baselined" in payload and "suppressed" in payload


# -- pragmas ----------------------------------------------------------------

def test_pragma_suppresses_on_same_line():
    src = ("def f():\n"
           "    try:\n"
           "        return 1\n"
           "    except:  # mxlint: disable=bare-except — fixture\n"
           "        return None\n")
    new, sup = mxlint.lint_source(src)
    assert new == [] and len(sup) == 1 and sup[0].rule == "bare-except"


def test_pragma_suppresses_from_comment_line_above():
    src = ("def f():\n"
           "    try:\n"
           "        return 1\n"
           "    # mxlint: disable=bare-except — justified in fixture\n"
           "    except:\n"
           "        return None\n")
    new, sup = mxlint.lint_source(src)
    assert new == [] and len(sup) == 1


def test_pragma_on_code_line_does_not_leak_to_next_line():
    # the pragma sits on the CODE line directly above the finding: only
    # standalone comment lines carry over, so this must still trip
    src = ("import time\n"
           "def f():\n"
           "    x = 1  # mxlint: disable=timing-pair\n"
           "    t0 = time.time()\n"
           "    return x, time.time() - t0\n")
    new, _sup = mxlint.lint_source(src)
    assert [f.rule for f in new] == ["timing-pair"]


def test_pragma_disable_all():
    src = ("import time\n"
           "def f():\n"
           "    t0 = time.time()  # mxlint: disable=all\n"
           "    return time.time() - t0\n")
    new, sup = mxlint.lint_source(src)
    assert new == [] and len(sup) == 1


def test_pragma_wrong_rule_does_not_suppress():
    src = ("def f():\n"
           "    try:\n"
           "        return 1\n"
           "    except:  # mxlint: disable=timing-pair\n"
           "        return None\n")
    new, _sup = mxlint.lint_source(src)
    assert [f.rule for f in new] == ["bare-except"]


# -- baseline ---------------------------------------------------------------

# The debt frozen by THIS PR.  Do not add entries: new code satisfies
# the rule or carries a justified pragma; this set only ever SHRINKS
# (delete an entry when its file's debt is paid).
#
# PR-6 grew it deliberately ONCE: introducing hidden-host-sync flagged
# every library `.asnumpy()`/`.item()` call site (~75).  The hot-path
# files (engine, register, resilience, trainer) plus the core API files
# (ndarray, flight, optimizer) were triaged to fixes/justified pragmas
# — they are NOT here, so new debt in them always fails — and the cold
# long tail (image augmenters, test utils, contrib, legacy kvstore/io)
# was frozen file-by-file below.
_FROZEN_BASELINE = {
    # PR-19 shrink: callback.py paid down — Speedometer's batch window
    # is measured through trace.span (histogram + timeline for free)
    ("timing-pair", "mxnet_tpu/gluon/contrib/estimator.py"),
    ("timing-pair", "mxnet_tpu/module/base_module.py"),
    ("hidden-host-sync", "mxnet_tpu/contrib/onnx/export.py"),
    ("hidden-host-sync", "mxnet_tpu/contrib/quantization.py"),
    ("hidden-host-sync", "mxnet_tpu/contrib/text/embedding.py"),
    ("hidden-host-sync", "mxnet_tpu/gluon/data/dataloader.py"),
    ("hidden-host-sync", "mxnet_tpu/gluon/data/vision/transforms.py"),
    ("hidden-host-sync", "mxnet_tpu/gluon/model_zoo/transformer.py"),
    # PR-7 shrink: gluon/utils.py (clip_global_norm batched to ONE
    # readback) and image.py (whole augmenter chain runs host-side with
    # a single pragma'd ingestion point) paid off their debt — the
    # freeze only ever loses entries, never regains them
    ("hidden-host-sync", "mxnet_tpu/io.py"),
    ("hidden-host-sync", "mxnet_tpu/kvstore.py"),
    # PR-20 shrink: metric.py paid down — the single _to_np ingestion
    # funnel is a deliberate eval-loop export boundary, pragma'd with
    # its justification
    ("hidden-host-sync", "mxnet_tpu/model.py"),
    ("hidden-host-sync", "mxnet_tpu/ndarray/contrib.py"),
    ("hidden-host-sync", "mxnet_tpu/ndarray/dgl.py"),
    ("hidden-host-sync", "mxnet_tpu/ndarray/ops_custom.py"),
    ("hidden-host-sync", "mxnet_tpu/ndarray/utils.py"),
    ("hidden-host-sync", "mxnet_tpu/numpy/__init__.py"),
    ("hidden-host-sync", "mxnet_tpu/rnn/rnn_cell.py"),
    # PR-15 shrink: sparse.py went device-backed (RowSparseNDArray holds
    # jax buffers, todense is a lazy scatter) — the only host crossings
    # left are the explicit asnumpy() export and the CSR ingestion
    # helper, both pragma'd at the boundary
    # PR-18 shrink: test_utils.py paid down — every comparison helper
    # reads back through the single pragma'd _as_numpy funnel
}


def test_shipped_baseline_is_frozen():
    """The baseline may only shrink: every shipped entry must be in the
    PR-5 freeze above, so debt in files added later can never hide."""
    baseline = mxlint.load_baseline()
    assert baseline <= _FROZEN_BASELINE, \
        f"baseline grew beyond the freeze: {baseline - _FROZEN_BASELINE}"


def test_baselined_file_is_not_a_new_finding(capsys):
    """File-level baseline semantics: the grandfathered timing pair in
    module/base_module.py lints as 'baselined', not 'new' (CLI exit 0)."""
    rc = mxlint.main([os.path.join(REPO, "mxnet_tpu", "module",
                                   "base_module.py")])
    capsys.readouterr()
    assert rc == 0
    findings, _sup = mxlint.lint_paths(
        [os.path.join(REPO, "mxnet_tpu", "module", "base_module.py")])
    new, old = mxlint.split_baselined(findings, mxlint.load_baseline())
    assert new == [] and len(old) >= 1


def test_register_py_flush_needs_no_timing_pragma():
    """The segment flush in ndarray/register.py is timed by a
    ``trace.span`` (so it lies in a profiler's trace), not by a bare
    clock pair behind a pragma: nothing to find, nothing suppressed,
    nothing baselined."""
    findings, sup = mxlint.lint_paths(
        [os.path.join(REPO, "mxnet_tpu", "ndarray", "register.py")])
    assert not any(f.rule == "timing-pair" for f in findings)
    assert not any(f.rule == "timing-pair" for f in sup)


# -- framework guarantees ---------------------------------------------------

def test_single_parse_pass_per_file(tmp_path, monkeypatch):
    """All seven rules ride ONE ast.parse per file (the reason the four
    walkers were consolidated)."""
    mxrules.declared_knobs(REPO)          # prime the knob-table cache
    files = []
    for i in range(3):
        p = tmp_path / f"m{i}.py"
        p.write_text("import time\nx = 1\n", encoding="utf-8")
        files.append(str(p))
    calls = []
    real_parse = ast.parse

    def counting_parse(*a, **k):
        calls.append(1)
        return real_parse(*a, **k)

    monkeypatch.setattr(ast, "parse", counting_parse)
    findings, _sup = mxlint.lint_paths(files)
    assert findings == []
    assert len(calls) == len(files), \
        f"{len(calls)} parses for {len(files)} files"


def test_parse_error_is_a_finding(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n", encoding="utf-8")
    findings, _sup = mxlint.lint_paths([str(p)])
    assert len(findings) == 1 and findings[0].rule == "parse-error"


def test_changed_mode_lists_python_files_only():
    files = mxlint._changed_files()
    assert isinstance(files, list)
    assert all(f.endswith(".py") for f in files)
    # fixture vectors trip their rules BY DESIGN; --changed (and the
    # precommit hook built on it) must never lint them
    assert not any("lint_fixtures" in f for f in files)


# -- rule-specific unit coverage beyond the fixtures ------------------------

def test_env_knob_rule_catches_undeclared_get_env():
    src = ("from mxnet_tpu.base import get_env\n"
           "v = get_env('MXTPU_BOGUS_KNOB')\n")
    new, _sup = mxlint.lint_source(src)
    assert [f.rule for f in new] == ["env-knob"]
    assert "MXTPU_BOGUS_KNOB" in new[0].message


def test_env_knob_rule_rejects_register_env_outside_base():
    src = ("from mxnet_tpu.base import register_env\n"
           "register_env('MXTPU_ROGUE', 1, int, 'rogue table entry')\n")
    new, _sup = mxlint.lint_source(src)
    assert [f.rule for f in new] == ["env-knob"]


def test_collective_safety_flags_else_branch():
    src = ("def f(rank, dist):\n"
           "    if rank == 0:\n"
           "        pass\n"
           "    else:\n"
           "        dist.barrier()\n")
    new, _sup = mxlint.lint_source(src)
    assert [f.rule for f in new] == ["collective-safety"]


def test_collective_safety_allows_uniform_conditions():
    src = ("def f(dist, num_workers):\n"
           "    if num_workers > 1:\n"
           "        dist.barrier()\n")
    new, _sup = mxlint.lint_source(src)
    assert new == []


def test_lock_discipline_module_scope():
    src = ("import threading\n"
           "_lock = threading.Lock()\n"
           "_inst = None\n"
           "def get():\n"
           "    global _inst\n"
           "    with _lock:\n"
           "        if _inst is None:\n"
           "            _inst = object()\n"
           "    return _inst\n"
           "def reset_unsafely():\n"
           "    global _inst\n"
           "    _inst = None\n")
    new, _sup = mxlint.lint_source(src)
    assert [f.rule for f in new] == ["lock-discipline"]
    assert "_inst" in new[0].message


def test_lru_rule_catches_classes_defined_inside_functions():
    # factory-built classes leak instances the same way (the old
    # test-suite walker covered this; regression from the port)
    src = ("import functools\n"
           "def make_op():\n"
           "    class Op:\n"
           "        @functools.lru_cache(maxsize=None)\n"
           "        def compile(self, key):\n"
           "            return key\n"
           "    return Op\n")
    new, _sup = mxlint.lint_source(src)
    assert [f.rule for f in new] == ["unbounded-lru-method"]


def test_lock_discipline_ignores_bare_annotations():
    # `self.x: int` (no value) is not a store and must not trip
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self._n = 0\n"
           "    def read(self):\n"
           "        with self._lock:\n"
           "            return self._n\n"
           "    def annotate(self):\n"
           "        self._n: int\n")
    new, _sup = mxlint.lint_source(src)
    assert new == []


def test_env_knob_catches_bare_environ_subscript():
    src = ("from os import environ\n"
           "v = environ['MXNET_BARE_SUBSCRIPT_KNOB']\n")
    new, _sup = mxlint.lint_source(src)
    assert [f.rule for f in new] == ["env-knob"]


def test_write_baseline_ignores_partial_scope(tmp_path, capsys):
    # freezing from a narrowed scope must not drop the grandfather
    # entries for everything outside it
    bl = str(tmp_path / "bl.json")
    rc = mxlint.main(["--baseline", bl, "--write-baseline",
                      os.path.join(REPO, "mxnet_tpu", "observability")])
    capsys.readouterr()
    assert rc == 0
    assert mxlint.load_baseline(bl) == _FROZEN_BASELINE


def test_lock_discipline_ignores_unguarded_only_attributes():
    # a lock that guards ONE attribute must not implicate the others
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self._guarded = 0\n"
           "        self._free = 0\n"
           "    def bump(self):\n"
           "        with self._lock:\n"
           "            self._guarded += 1\n"
           "    def poke(self):\n"
           "        self._free += 1\n")
    new, _sup = mxlint.lint_source(src)
    assert new == []


# -- PR-6: interprocedural engine -------------------------------------------

def _project(*files):
    """Build a Project from (relpath, source) pairs — the multi-file
    unit-test entry the fixtures (single-file) can't exercise."""
    return mxgraph.build_project(
        [(rp, ast.parse(src)) for rp, src in files])


def test_call_graph_resolves_self_methods():
    p = _project(("pkg/a.py",
                  "class C:\n"
                  "    def top(self):\n"
                  "        return self.helper()\n"
                  "    def helper(self):\n"
                  "        return 1\n"))
    ff = p.functions["pkg/a.py::C.top"]
    edges = [p.resolve(ff, cs.desc) for cs in ff.calls]
    assert "pkg/a.py::C.helper" in edges


def test_call_graph_resolves_alias_imports_across_files():
    p = _project(
        ("pkg/util.py", "def work():\n    return 1\n"),
        ("pkg/main.py",
         "from pkg.util import work as w\n"
         "def run():\n    return w()\n"))
    ff = p.functions["pkg/main.py::run"]
    assert [p.resolve(ff, cs.desc) for cs in ff.calls] == \
        ["pkg/util.py::work"]


def test_call_graph_resolves_module_attr_calls():
    p = _project(
        ("pkg/__init__.py", ""),
        ("pkg/dist.py", "def barrier_all():\n    return 0\n"),
        ("pkg/train.py",
         "from pkg import dist\n"
         "def sync():\n    return dist.barrier_all()\n"))
    ff = p.functions["pkg/train.py::sync"]
    assert [p.resolve(ff, cs.desc) for cs in ff.calls] == \
        ["pkg/dist.py::barrier_all"]


def test_call_graph_resolves_relative_imports():
    p = _project(
        ("pkg/sub/helper.py", "def f():\n    return 1\n"),
        ("pkg/sub/user.py",
         "from .helper import f\n"
         "def g():\n    return f()\n"))
    ff = p.functions["pkg/sub/user.py::g"]
    assert [p.resolve(ff, cs.desc) for cs in ff.calls] == \
        ["pkg/sub/helper.py::f"]


def test_call_graph_cycle_is_safe():
    p = _project(("pkg/a.py",
                  "def f():\n    return g()\n"
                  "def g():\n    return f()\n"))
    # both searches must terminate on the f <-> g cycle
    assert p.find_collective("pkg/a.py::f") is None
    reach = p.reachable(["pkg/a.py::f"])
    assert set(reach) == {"pkg/a.py::f", "pkg/a.py::g"}


def test_call_depth_bound_cuts_deep_chains():
    # f0 -> f1 -> ... -> f9 -> barrier(); the default bound must stop
    # well before depth 9, so the deep collective stays invisible
    lines = ["def f9(d):\n    return d.barrier()\n"]
    for i in range(8, -1, -1):
        lines.append(f"def f{i}(d):\n    return f{i + 1}(d)\n")
    p = _project(("pkg/deep.py", "".join(lines)))
    assert p.find_collective("pkg/deep.py::f9") is not None
    assert p.find_collective("pkg/deep.py::f0") is None


def test_cross_file_transitive_collective_is_flagged():
    """The repo-wide blind spot PR-5 had: branch in one FILE, collective
    wrapper in another."""
    src_a = ("def refresh(dist):\n"
             "    return dist.allgather_host([1])\n")
    src_b = ("from pkg.metrics import refresh\n"
             "def checkpoint(dist, rank):\n"
             "    if rank == 0:\n"
             "        refresh(dist)\n")
    p = _project(("pkg/metrics.py", src_a), ("pkg/train.py", src_b))
    rule = next(r for r in mxrules.make_rules(REPO)
                if r.name == "collective-safety")
    findings = rule.project_check(p)
    assert [f.path for f in findings] == ["pkg/train.py"]
    assert findings[0].reason and \
        "pkg/metrics.py::refresh" in " ".join(findings[0].reason)


def test_finding_reason_chain_and_stable_id():
    new, _sup = mxlint.lint_source(
        _fixture("hidden_host_sync_bad.py"),
        relpath="tests/lint_fixtures/hidden_host_sync_bad.py")
    f = new[0]
    assert f.reason, "escalated finding must carry its call chain"
    assert any("train_step" in r for r in f.reason)
    assert f.id == ("hidden-host-sync:tests/lint_fixtures/"
                    "hidden_host_sync_bad.py:_log_loss")
    d = f.as_dict()
    assert d["id"] == f.id and d["symbol"] == "_log_loss" and d["reason"]


def test_lock_discipline_recognizes_acquire_release_regions():
    # the PR-5 follow-up: an explicit pair (incl. try/finally) is a held
    # region — the write below is GUARDED, not a violation
    src = ("import threading\n"
           "_lock = threading.Lock()\n"
           "_state = {}\n"
           "def get(k):\n"
           "    with _lock:\n"
           "        return _state.get(k)\n"
           "def put(k, v):\n"
           "    _lock.acquire()\n"
           "    try:\n"
           "        _state[k] = v\n"
           "    finally:\n"
           "        _lock.release()\n")
    new, _sup = mxlint.lint_source(src)
    assert new == [], new


def test_lock_order_inversion_across_methods():
    new, _sup = mxlint.lint_source(
        _fixture("lock_order_bad.py"),
        relpath="tests/lint_fixtures/lock_order_bad.py")
    assert len(new) == 1 and "inversion" in new[0].message
    assert len(new[0].reason) == 2      # one entry per conflicting order


def test_call_graph_reexport_cycle_dead_ends():
    # `from b import f` / `from a import f` re-export cycle: resolution
    # must dead-end (depth bound), not recurse to a crash
    p = _project(
        ("pkg/a.py", "from pkg.b import f\ndef call():\n    return f()\n"),
        ("pkg/b.py", "from pkg.a import f\n"))
    ff = p.functions["pkg/a.py::call"]
    assert p.resolve(ff, ff.calls[0].desc) is None


def test_nested_class_methods_do_not_pollute_outer_class():
    p = _project(("pkg/a.py",
                  "class Outer:\n"
                  "    class Inner:\n"
                  "        def meth(self):\n            return 1\n"
                  "    def top(self):\n"
                  "        return self.meth()\n"))
    ff = p.functions["pkg/a.py::Outer.top"]
    assert p.resolve(ff, ff.calls[0].desc) is None   # no invented edge
    # ...while the inner class still resolves its own methods
    p2 = _project(("pkg/b.py",
                   "class Outer:\n"
                   "    class Inner:\n"
                   "        def a(self):\n            return self.b()\n"
                   "        def b(self):\n            return 2\n"))
    ffa = p2.functions["pkg/b.py::Outer.Inner.a"]
    assert p2.resolve(ffa, ffa.calls[0].desc) == "pkg/b.py::Outer.Inner.b"


def test_branch_local_acquire_does_not_leak_to_other_path():
    # acquire() in one if-arm must not look held in the mutually
    # exclusive path — that would invent a re-acquire deadlock finding
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "    def f(self, persist):\n"
           "        if persist:\n"
           "            self._lock.acquire()\n"
           "            return\n"
           "        with self._lock:\n"
           "            pass\n")
    new, _sup = mxlint.lint_source(src)
    assert not any("re-acquires" in f.message for f in new), new


def test_function_local_locks_do_not_alias_across_functions():
    # two functions each with their OWN local a/b locks in opposite
    # nesting order: distinct objects, no deadlock, no finding —
    # module-LEVEL locks in opposite orders must still be flagged
    local = ("import threading\n"
             "def f():\n"
             "    a_lock = threading.Lock(); b_lock = threading.Lock()\n"
             "    with a_lock:\n        with b_lock:\n            pass\n"
             "def g():\n"
             "    a_lock = threading.Lock(); b_lock = threading.Lock()\n"
             "    with b_lock:\n        with a_lock:\n            pass\n")
    new, _sup = mxlint.lint_source(local)
    assert not any("inversion" in f.message for f in new), new
    glob = ("import threading\n"
            "_a_lock = threading.Lock()\n_b_lock = threading.Lock()\n"
            "def f():\n"
            "    with _a_lock:\n        with _b_lock:\n            pass\n"
            "def g():\n"
            "    with _b_lock:\n        with _a_lock:\n            pass\n")
    new, _sup = mxlint.lint_source(glob)
    assert any("inversion" in f.message for f in new), new


def test_fix_refuses_raise_in_lock_region():
    # a raise between the pair leaves the lock HELD in the original;
    # `with` would release it — behavior change, fixer must refuse
    declared = mxrules.declared_knobs(REPO)
    src = ("import threading\n_lock = threading.Lock()\n"
           "def f(x):\n"
           "    _lock.acquire()\n"
           "    if x < 0:\n        raise ValueError(x)\n"
           "    _lock.release()\n")
    fixed, fixes = mxfix.fix_source(src, "mxnet_tpu/demo.py", declared)
    assert fixed == src and fixes == []


def test_lock_reacquire_within_one_function():
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "    def f(self):\n"
           "        with self._lock:\n"
           "            with self._lock:\n"
           "                return 1\n")
    new, _sup = mxlint.lint_source(src)
    assert any("re-acquires" in f.message for f in new), new


def test_collective_safety_transitive_from_elif_branch():
    src = ("def inner(dist):\n    return dist.barrier()\n"
           "def go(dist, rank, mode):\n"
           "    if mode == 'a':\n        pass\n"
           "    elif rank == 0:\n"
           "        inner(dist)\n")
    new, _sup = mxlint.lint_source(src)
    assert [f.rule for f in new] == ["collective-safety"]
    assert new[0].line == 7


def test_hot_path_marker_is_runtime_noop():
    from mxnet_tpu.base import hot_path

    @hot_path("dispatch")
    def f(x):
        return x + 1

    assert f(1) == 2 and f.__mxlint_hot_path__ == "dispatch"
    with pytest.raises(ValueError):
        hot_path("bogus")


def test_repo_hot_roots_are_declared():
    """The rules are only as good as their roots: the engine dispatch
    path, both trainer steps, and (PR-7) the serving dispatch/assembly
    entry points must be marked."""
    new, baselined = mxlint.check_repo()
    del new, baselined                  # ensure the cached run exists
    items = []
    for path in mxlint.iter_py_files([mxlint.DEFAULT_TARGET]):
        rel = os.path.relpath(path, REPO).replace(os.sep, "/")
        if rel in ("mxnet_tpu/engine.py", "mxnet_tpu/ndarray/register.py",
                   "mxnet_tpu/parallel/trainer.py",
                   "mxnet_tpu/parallel/resilience.py",
                   "mxnet_tpu/parallel/dist.py",
                   "mxnet_tpu/gluon/trainer.py",
                   "mxnet_tpu/serving/server.py",
                   "mxnet_tpu/serving/batcher.py",
                   "mxnet_tpu/serving/buckets.py"):
            with open(path, encoding="utf-8") as f:
                items.append((rel, ast.parse(f.read())))
    p = mxgraph.build_project(items)
    roots = set(p.hot_roots(("dispatch", "step")))
    assert "mxnet_tpu/engine.py::Engine.on_push" in roots
    assert "mxnet_tpu/ndarray/register.py::_try_defer" in roots
    assert "mxnet_tpu/parallel/trainer.py::ShardedTrainer.step" in roots
    assert "mxnet_tpu/parallel/resilience.py::ResilientTrainer.step" \
        in roots
    # the serving path: per-batch compiled dispatch + batch assembly
    assert "mxnet_tpu/serving/server.py::ModelServer._dispatch_batch" \
        in roots
    assert "mxnet_tpu/serving/batcher.py::Batcher._assemble" in roots
    assert "mxnet_tpu/serving/buckets.py::Bucketer.assemble" in roots
    # the generation path (PR-14): per-step decode + prompt prefill —
    # graph/bucket resolution stays OUTSIDE these roots by design
    assert ("mxnet_tpu/serving/server.py::GenerationServer._decode_step"
            in roots)
    assert ("mxnet_tpu/serving/server.py::GenerationServer._prefill"
            in roots)
    # the sparse exchange path (PR-15): the per-step coalesced
    # row-sparse gradient exchange and its DCN collective
    assert "mxnet_tpu/parallel/dist.py::allgather_rows" in roots
    assert ("mxnet_tpu/gluon/trainer.py::Trainer._exchange_row_sparse"
            in roots)


def test_two_pass_full_repo_under_five_seconds():
    """Perf gate: the whole two-pass analysis (parse + facts + walk +
    interprocedural phase + the PR-20 CFG tier, all twelve rules) stays
    under ~5s so the lint keeps earning its place in tier-1.  The CFG
    pass only builds graphs for functions whose lexical prescan shows a
    protocol acquire, a thread, or a lock — that is what keeps the
    budget honest."""
    # mxlint: disable=timing-pair — this test measures the lint itself
    t0 = time.perf_counter()
    findings, _sup = mxlint.lint_paths([mxlint.DEFAULT_TARGET])
    elapsed = time.perf_counter() - t0
    assert elapsed < 5.0, f"two-pass+CFG repo lint took {elapsed:.2f}s"
    assert findings  # sanity: the run actually analyzed the tree


# -- PR-6: --fix ------------------------------------------------------------

_FIXABLE = ('"""doc."""\n'
            "import os\n"
            "import threading\n"
            "_lock = threading.Lock()\n"
            "_state = {}\n"
            "def knob():\n"
            '    return os.environ.get("MXNET_ENGINE_BULK_SIZE", "15")\n'
            "def put(k, v):\n"
            "    _lock.acquire()\n"
            "    _state[k] = v\n"
            "    _lock.release()\n")


def test_fix_rewrites_env_read_and_lock_pair():
    declared = mxrules.declared_knobs(REPO)
    fixed, fixes = mxfix.fix_source(_FIXABLE, "mxnet_tpu/demo.py",
                                    declared)
    kinds = sorted({f.kind for f in fixes})
    assert kinds == ["env-read", "with-lock"]
    assert 'get_env("MXNET_ENGINE_BULK_SIZE")' in fixed
    assert "from .base import get_env" in fixed
    assert "with _lock:" in fixed and ".acquire()" not in fixed
    ast.parse(fixed)                    # the rewrite is valid python


def test_fix_is_idempotent_and_validated_by_relint():
    declared = mxrules.declared_knobs(REPO)
    fixed, _ = mxfix.fix_source(_FIXABLE, "mxnet_tpu/demo.py", declared)
    again, fixes2 = mxfix.fix_source(fixed, "mxnet_tpu/demo.py",
                                     declared)
    assert again == fixed and fixes2 == []
    # the fixed tree lints clean where the original tripped env-knob
    new_before, _ = mxlint.lint_source(_FIXABLE,
                                       relpath="mxnet_tpu/demo.py")
    new_after, _ = mxlint.lint_source(fixed, relpath="mxnet_tpu/demo.py")
    assert any(f.rule == "env-knob" for f in new_before)
    assert not any(f.rule == "env-knob" for f in new_after)


def test_fix_leaves_unsafe_pairs_alone():
    # early return between the pair: the lock LEAKS there — a rewrite
    # to `with` would change behavior, so the fixer must refuse
    # (register.py's release/re-acquire dance hits the same guard)
    src = ("import threading\n"
           "_lock = threading.Lock()\n"
           "_state = {}\n"
           "def leaky(k):\n"
           "    _lock.acquire()\n"
           "    if k in _state:\n"
           "        return _state[k]\n"
           "    _lock.release()\n")
    declared = mxrules.declared_knobs(REPO)
    fixed, fixes = mxfix.fix_source(src, "mxnet_tpu/demo.py", declared)
    assert fixed == src and fixes == []


def test_fix_handles_nested_same_line_env_reads():
    # a declared-knob read as another's default arg: the OUTER span is
    # rewritten in one shot; rewriting inner-first would shift the line
    # and make the outer span eat trailing code
    declared = mxrules.declared_knobs(REPO)
    src = ('import os\n'
           'v = os.environ.get("MXNET_ENGINE_BULK_SIZE", '
           'os.environ.get("MXNET_ENGINE_TYPE")) or "x"\n')
    fixed, _fixes = mxfix.fix_source(src, "mxnet_tpu/demo.py", declared)
    assert 'or "x"' in fixed and fixed.count("get_env(") == 1
    ast.parse(fixed)


def test_fix_refuses_multiline_strings_in_lock_region():
    # raw-line re-indent inside a triple-quoted literal would change the
    # string's VALUE — the fixer must refuse
    declared = mxrules.declared_knobs(REPO)
    src = ('import threading\n'
           '_lock = threading.Lock()\n'
           'def f():\n'
           '    _lock.acquire()\n'
           '    msg = """a\nb"""\n'
           '    _lock.release()\n'
           '    return msg\n')
    fixed, fixes = mxfix.fix_source(src, "mxnet_tpu/demo.py", declared)
    assert fixed == src and fixes == []


def test_fix_honors_disable_pragmas():
    # a site the author pragma'd as intentionally raw must not be
    # rewritten (and must not wedge the --fix --dry-run precommit gate)
    declared = mxrules.declared_knobs(REPO)
    src = ('import os\n'
           '# mxlint: disable=env-knob — need the raw string\n'
           'v = os.environ.get("MXNET_ENGINE_TYPE")\n'
           'import threading\n'
           '_lock = threading.Lock()\n'
           'def g(d, k, v2):\n'
           '    # mxlint: disable=lock-discipline — measured pair\n'
           '    _lock.acquire()\n'
           '    d[k] = v2\n'
           '    _lock.release()\n')
    fixed, fixes = mxfix.fix_source(src, "mxnet_tpu/demo.py", declared)
    assert fixed == src and fixes == []


def test_fix_json_stdout_stays_parseable(tmp_path, capsys):
    import json as _json
    p = tmp_path / "demo.py"
    p.write_text(_FIXABLE, encoding="utf-8")
    rc = mxlint.main(["--json", "--fix", str(p)])
    del rc
    out = capsys.readouterr().out
    _json.loads(out)                    # one clean JSON document


def test_fix_dry_run_cli_reports_without_writing(tmp_path, capsys):
    p = tmp_path / "demo.py"
    p.write_text(_FIXABLE, encoding="utf-8")
    rc = mxlint.main(["--fix", "--dry-run", str(p)])
    out = capsys.readouterr().out
    assert rc == 1 and "fix" in out and "---" not in p.read_text() \
        and p.read_text() == _FIXABLE       # nothing written
    rc = mxlint.main(["--fix", str(p)])
    capsys.readouterr()
    assert p.read_text() != _FIXABLE        # now it wrote
    rc = mxlint.main(["--fix", "--dry-run", str(p)])
    capsys.readouterr()
    assert rc == 0                          # idempotent: nothing pending


def test_shipped_tree_has_no_pending_fixes(capsys):
    rc = mxlint.main(["--fix", "--dry-run"])
    capsys.readouterr()
    assert rc == 0


# -- env-knob table / README sync -------------------------------------------

def test_knob_table_covers_all_declared_knobs():
    rows = mxlint.knob_rows()
    names = [r["name"] for r in rows]
    assert len(names) == len(set(names))
    assert "MXNET_ENGINE_BULK_SIZE" in names
    assert "MXTPU_DIST_TIMEOUT" in names
    assert "MXTPU_FLIGHT_STEPS" in names
    # every row documents itself
    assert all(r["help"] for r in rows), \
        [r["name"] for r in rows if not r["help"]]


def test_readme_knob_table_in_sync():
    """The README's env-knob reference is GENERATED
    (``python -m mxnet_tpu.tools.mxlint --knobs-md``) — drift fails."""
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    begin, end = "<!-- mxlint-knobs:begin -->", "<!-- mxlint-knobs:end -->"
    assert begin in readme and end in readme
    block = readme.split(begin)[1].split(end)[0]
    assert block.strip() == mxlint.knob_table_markdown().strip(), \
        "README knob table is stale: regenerate with " \
        "`python -m mxnet_tpu.tools.mxlint --knobs-md`"


def test_no_dead_suite_and_readme_names_only_tracked_files():
    """`BENCHMARK.json` + `benchmarks/` is the one yardstick: no second
    suite (`bench.py`) and no banked record (`*_rNN.json`) at the root,
    and every `.py` path the README backticks is a file of the tree
    (whole, by its tail under a package, or as a glob)."""
    import fnmatch
    import re
    import subprocess
    try:
        tracked = subprocess.run(
            ["git", "-C", REPO, "ls-files"], capture_output=True,
            text=True, check=True).stdout.split()
    except (OSError, subprocess.CalledProcessError):
        tracked = []
    if not tracked:     # a checkout without its .git: the files on disk
        tracked = [os.path.relpath(os.path.join(d, f), REPO)
                   for d, _, fs in os.walk(REPO) for f in fs]
    root = [f for f in tracked if "/" not in f]
    assert "bench.py" not in root
    assert not [f for f in root if re.search(r"_r[0-9][0-9]\.json$", f)]
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        named = set(re.findall(r"`([^`\s]*\.py)`", f.read()))
    missing = sorted(
        n for n in named
        if not any(f == n or f.endswith("/" + n) or fnmatch.fnmatch(f, n)
                   for f in tracked))
    assert not missing, f"README names files not in the tree: {missing}"

# -- PR-20: the flow-sensitive (CFG) tier ------------------------------------

from mxnet_tpu.tools.mxlint import cfg as mxcfg  # noqa: E402


def _cfg_of(src: str) -> "mxcfg.CFG":
    mod = ast.parse(src)
    fn = next(n for n in mod.body if isinstance(n, ast.FunctionDef))
    return mxcfg.build_cfg(fn)


def _reachable(cfg) -> set:
    """Block ids reachable from entry, following normal successors plus
    the exception edge of any block holding a may-raise event — the same
    edge set the analyses walk."""
    seen, stack = set(), [cfg.entry]
    while stack:
        b = stack.pop()
        if b in seen:
            continue
        seen.add(b)
        blk = cfg.block(b)
        stack.extend(blk.succs)
        if blk.exc is not None and any(e.kind in mxcfg.MAY_RAISE
                                       for e in blk.events):
            stack.append(blk.exc)
    return seen


def _rules_of(src: str):
    new, _sup = mxlint.lint_source(src, relpath="mxnet_tpu/snip.py")
    return sorted({f.rule for f in new}), new


# CFG structure: the lowering invariants every flow verdict rests on.

def test_cfg_finally_body_is_duplicated_per_unwind_kind():
    """``finally`` lowers by duplication: one copy on fall-through, one
    on the return unwind, one on the exception edge — a cleanup call
    must appear on EVERY way out or the leak search would thread paths
    around it."""
    g = _cfg_of("def f(p, work, cleanup):\n"
                "    try:\n"
                "        if p:\n"
                "            return work()\n"
                "        work()\n"
                "    finally:\n"
                "        cleanup()\n")
    copies = [e for _b, _i, e in g.events()
              if e.kind == "call" and isinstance(e.node.func, ast.Name)
              and e.node.func.id == "cleanup"]
    assert len(copies) == 3
    # without a return in the try there is no return-unwind copy
    g = _cfg_of("def f(work, cleanup):\n"
                "    try:\n"
                "        work()\n"
                "    finally:\n"
                "        cleanup()\n")
    copies = [e for _b, _i, e in g.events()
              if e.kind == "call" and isinstance(e.node.func, ast.Name)
              and e.node.func.id == "cleanup"]
    assert len(copies) == 2


def test_cfg_with_region_has_one_enter_two_exits():
    """``with`` emits one enter and two exits (normal + exceptional
    unwind) so a lock's held-region closes on both ways out."""
    g = _cfg_of("def g(cm, work):\n"
                "    with cm:\n"
                "        work()\n")
    kinds = [e.kind for _b, _i, e in g.events()]
    assert kinds.count("with-enter") == 1
    assert kinds.count("with-exit") == 2


def test_cfg_branch_raise_and_exit_edges():
    g = _cfg_of("def r(x):\n"
                "    if x:\n"
                "        raise ValueError(x)\n"
                "    return x\n")
    assert len(g.branches) == 1
    test, t_succ, f_succ = next(iter(g.branches.values()))
    assert isinstance(test, ast.expr) and t_succ != f_succ
    rr = _reachable(g)
    assert g.raise_id in rr and g.exit_id in rr


def test_cfg_handler_coverage_gates_the_raise_exit():
    """A catch-all handler kills the outer exception edge; a specific
    one leaves it live — the exact distinction the partial-catch leak
    findings ride on."""
    g = _cfg_of("def swallow(work):\n"
                "    try:\n"
                "        work()\n"
                "    except BaseException:\n"
                "        pass\n"
                "    return 1\n")
    assert g.raise_id not in _reachable(g)
    g = _cfg_of("def partial(work):\n"
                "    try:\n"
                "        work()\n"
                "    except ValueError:\n"
                "        pass\n")
    assert g.raise_id in _reachable(g)


def test_cfg_loop_break_continue_edges_terminate():
    g = _cfg_of("def loop(xs, fn):\n"
                "    for x in xs:\n"
                "        if x:\n"
                "            continue\n"
                "        if fn(x):\n"
                "            break\n"
                "        fn(x)\n"
                "    return 0\n")
    assert len(g.branches) == 2
    assert g.exit_id in _reachable(g)


def test_cfg_generator_yield_is_an_event_and_terminates():
    g = _cfg_of("def gen(xs):\n"
                "    for x in xs:\n"
                "        yield x\n")
    kinds = [e.kind for _b, _i, e in g.events()]
    assert "yield" in kinds
    assert g.exit_id in _reachable(g)


# resource-leak: path-sensitivity beyond what the fixtures cover.

def test_leak_through_break_edge():
    got, _ = _rules_of("def pump(kv, reqs):\n"
                       "    for r in reqs:\n"
                       "        tbl = kv.reserve(r.rid, r.n)\n"
                       "        if r.stop:\n"
                       "            break\n"
                       "        kv.release(r.rid)\n")
    assert got == ["resource-leak"]


def test_leak_through_continue_edge():
    got, _ = _rules_of("def drain(kv, reqs):\n"
                       "    for r in reqs:\n"
                       "        tbl = kv.reserve(r.rid, r.n)\n"
                       "        if tbl.full:\n"
                       "            continue\n"
                       "        kv.release(r.rid)\n")
    assert got == ["resource-leak"]


def test_leak_through_explicit_raise():
    got, new = _rules_of("def guard(tracer, ok):\n"
                         "    sp = tracer.begin(\"step\")\n"
                         "    if not ok:\n"
                         "        raise ValueError(\"bad input\")\n"
                         "    sp.finish()\n")
    assert got == ["resource-leak"]
    assert "exception exit" in new[0].message


def test_leak_past_partial_catch():
    """``except ValueError`` does not cover the exception edge — any
    OTHER exception still threads past both finishes."""
    got, _ = _rules_of("def submit(tracer, admission, req):\n"
                       "    sp = tracer.begin(\"submit\")\n"
                       "    try:\n"
                       "        admission.enqueue(req)\n"
                       "    except ValueError:\n"
                       "        sp.finish()\n"
                       "        raise\n"
                       "    sp.finish()\n")
    assert got == ["resource-leak"]


def test_nested_handlers_with_catch_all_are_clean():
    got, _ = _rules_of("def robust(tracer, work):\n"
                       "    sp = tracer.begin(\"outer\")\n"
                       "    try:\n"
                       "        try:\n"
                       "            work()\n"
                       "        except ValueError:\n"
                       "            sp.annotate(err=True)\n"
                       "            raise\n"
                       "    except BaseException:\n"
                       "        sp.finish()\n"
                       "        raise\n"
                       "    sp.finish()\n")
    assert got == []


def test_twin_guard_prunes_conditional_binder():
    """``rb = None if span is None else begin(...)``: rb exists exactly
    when span does, so a later ``if span is not None:`` guard closes
    rb's obligation on both arms — the ``_dispatch_batch`` shape."""
    got, _ = _rules_of(
        "def fanout(tracer, span, work):\n"
        "    rb = None if span is None else "
        "tracer.begin(\"readback\", parent=span)\n"
        "    try:\n"
        "        work()\n"
        "    finally:\n"
        "        if span is not None:\n"
        "            rb.finish()\n")
    assert got == []


def test_dotted_attribute_guard_prunes_absent_arm():
    """``req.trace = begin()`` binds the dotted path; the handler's
    ``if req.trace is not None:`` guard must prune the absent arm —
    the ``ModelServer.submit`` shape this PR fixed."""
    got, _ = _rules_of("def submit(tracer, req, admission):\n"
                       "    req.trace = tracer.begin(\"req\")\n"
                       "    try:\n"
                       "        admission.enqueue(req)\n"
                       "    except BaseException:\n"
                       "        if req.trace is not None:\n"
                       "            req.trace.finish()\n"
                       "        raise\n"
                       "    return req\n")
    assert got == []


def test_transfer_evidence_cites_missing_callee_release():
    """A transfer-that-raised resolves the callee through the call
    graph: no reachable release -> the reason says so."""
    got, new = _rules_of("def enqueue(tracer, admission, req):\n"
                         "    req.trace = tracer.begin(\"req\")\n"
                         "    _admit(admission, req)\n"
                         "\n"
                         "def _admit(admission, req):\n"
                         "    admission.push(req)\n")
    assert got == ["resource-leak"]
    joined = " ".join(new[0].reason)
    assert "raised before taking ownership" in joined
    assert "mxnet_tpu/snip.py::_admit" in joined
    assert "performs no span release" in joined


def test_transfer_evidence_cites_where_ownership_lands():
    got, new = _rules_of("def handoff(tracer, req):\n"
                         "    req.trace = tracer.begin(\"req\")\n"
                         "    finalize(req)\n"
                         "\n"
                         "def finalize(req):\n"
                         "    if req.trace is not None:\n"
                         "        req.trace.finish()\n")
    assert got == ["resource-leak"]   # the exception edge still leaks
    joined = " ".join(new[0].reason)
    assert "ownership lands in mxnet_tpu/snip.py::finalize" in joined
    assert "releases at mxnet_tpu/snip.py:7" in joined


def test_find_release_walks_the_call_chain():
    p = _project(("pkg/a.py",
                  "def owner(req):\n"
                  "    hand(req)\n"
                  "def hand(req):\n"
                  "    req.trace.finish()\n"))
    chain, line = p.find_release("pkg/a.py::owner", "span")
    assert chain == ("pkg/a.py::owner", "pkg/a.py::hand") and line == 4
    assert p.find_release("pkg/a.py::owner", "kv-block") is None


# thread-lifecycle: the shapes the fixture pair can't isolate.

def test_inline_thread_start_is_fire_and_forget():
    got, new = _rules_of("import threading\n"
                         "def kick(fn):\n"
                         "    threading.Thread("
                         "target=fn, daemon=True).start()\n")
    assert got == ["thread-lifecycle"]
    assert "fire-and-forget" in new[0].message


def test_class_thread_flagged_when_only_the_starter_reads_it():
    got, _ = _rules_of("import threading\n"
                       "class P:\n"
                       "    def __init__(self):\n"
                       "        self._t = threading.Thread("
                       "target=self._run, daemon=True)\n"
                       "    def start(self):\n"
                       "        self._t.start()\n"
                       "    def _run(self):\n"
                       "        pass\n")
    assert got == ["thread-lifecycle"]


def test_class_thread_reader_counts_as_managed_teardown():
    """Any reader of the attribute OTHER than the starter (the
    alias-join idiom never names the attr in a retire verb) suppresses
    the module-level finding."""
    got, _ = _rules_of("import threading\n"
                       "class P:\n"
                       "    def __init__(self):\n"
                       "        self._t = threading.Thread("
                       "target=self._run, daemon=True)\n"
                       "    def start(self):\n"
                       "        self._t.start()\n"
                       "    def _run(self):\n"
                       "        pass\n"
                       "    def alive(self):\n"
                       "        return self._t.is_alive()\n")
    assert got == []


# blocking-under-lock: the interprocedural half.

def test_blocking_under_lock_across_files_cites_the_chain():
    p = _project(
        ("pkg/util.py", "def wait_done(q):\n    return q.get()\n"),
        ("pkg/srv.py",
         "import threading\n"
         "from pkg.util import wait_done\n"
         "class C:\n"
         "    def __init__(self):\n"
         "        self._lock = threading.Lock()\n"
         "    def poll(self, q):\n"
         "        with self._lock:\n"
         "            return wait_done(q)\n"))
    rule = next(r for r in mxrules.make_rules(REPO)
                if r.name == "blocking-under-lock")
    fs = rule.project_check(p)
    assert [(f.path, f.line) for f in fs] == [("pkg/srv.py", 8)]
    joined = " ".join(fs[0].reason)
    assert "pkg/srv.py::C.poll -> pkg/util.py::wait_done" in joined
    assert fs[0].hops == ("pkg/srv.py:8", "pkg/util.py:2")


# hops: every flow finding carries its replayable program-point path.

def test_flow_findings_carry_hops_in_dict_and_json(capsys):
    import json as _json
    new, _sup = mxlint.lint_source(
        _fixture("resource_leak_bad.py"),
        relpath="tests/lint_fixtures/resource_leak_bad.py")
    f = new[0]
    assert f.hops, "flow finding must carry its path"
    for hop in f.hops:
        path, _, line = hop.rpartition(":")
        assert path and line.isdigit()
    d = f.as_dict()
    assert d["hops"] == list(f.hops)
    # EVERY flow finding owes at least the obligation's birth line —
    # including start-then-fall-off-the-end, where the walked path
    # itself crosses no further events
    for stem in ("resource_leak", "thread_lifecycle",
                 "blocking_under_lock"):
        fs, _s = mxlint.lint_source(
            _fixture(f"{stem}_bad.py"),
            relpath=f"tests/lint_fixtures/{stem}_bad.py")
        assert fs and all(x.hops for x in fs), (stem, fs)
    # and the CLI --json payload round-trips them
    rc = mxlint.main(["--json",
                      os.path.join(FIXTURES, "resource_leak_bad.py")])
    payload = _json.loads(capsys.readouterr().out)
    assert rc == 1
    assert payload["new"][0]["hops"] == list(f.hops)
