"""graftcheck static-analyzer tests (docs/STATIC_ANALYSIS.md).

Per rule: a seeded violation MUST be caught and the known-good repo
idiom MUST pass clean. Then the repo-level contracts: the ~67
compile-factory sites across models/, ops/ and parallel/ pass GC01
(floor 60 asserted below), the atomic
write helpers in io/ pass GC03, the whole tree gates clean with an
EMPTY baseline, the baseline flags stale entries, and graftcheck runs
clean on its own source (self-lint).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from hivemall_tpu.tools.graftcheck import run_paths
from hivemall_tpu.tools.graftcheck.engine import (gate, load_baseline,
                                                  scan_file,
                                                  write_baseline)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "hivemall_tpu")


@pytest.fixture(scope="module")
def extended_scan():
    """ONE scan of the full default CI surface (package + tests/ +
    graft entry), shared by every repo-clean pin below —
    five independent repo-wide scans cost ~75 s of tier-1 wall on the
    2-core container and the suite runs against an 870 s budget."""
    paths = [PKG, os.path.join(REPO, "tests"),
             os.path.join(REPO, "__graft_entry__.py")]
    return run_paths([p for p in paths if os.path.exists(p)], root=REPO)


@pytest.fixture(scope="module")
def repo_index():
    """ONE interprocedural index over the repo, shared by the GC10/GC11
    non-vacuity pins (same wall-budget rationale as extended_scan)."""
    from hivemall_tpu.tools.graftcheck import engine as eng
    from hivemall_tpu.tools.graftcheck.rules import collect_project
    ctxs = []
    for rel, ap in _repo_files().items():
        ctx, err = eng._parse_one(ap, rel)
        if ctx is not None:
            ctxs.append(ctx)
    idx = collect_project(ctxs).interproc
    assert idx is not None
    return idx


def check_src(tmp_path, src, rel="pkg/mod.py"):
    """Write one module into a scratch tree and scan it."""
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return run_paths([str(tmp_path)], root=str(tmp_path))


def codes(findings):
    return sorted({f.code for f in findings})


# -- GC01 retrace-hazard ----------------------------------------------------

def test_gc01_per_call_jit_flagged(tmp_path):
    out = check_src(tmp_path, """
        import jax
        def predict(f, x):
            g = jax.jit(f)
            return g(x)
    """)
    assert codes(out) == ["GC01"]


def test_gc01_immediate_invoke_flagged(tmp_path):
    out = check_src(tmp_path, """
        import jax
        def predict(f, x):
            return jax.jit(f)(x)
    """)
    assert codes(out) == ["GC01"]


def test_gc01_loop_creation_flagged(tmp_path):
    out = check_src(tmp_path, """
        import jax
        def build_all(fns):
            out = []
            for f in fns:
                out.append(jax.jit(f))
            return out
    """)
    assert codes(out) == ["GC01"]


def test_gc01_nested_lru_cache_flagged(tmp_path):
    out = check_src(tmp_path, """
        from functools import lru_cache
        def make():
            @lru_cache(maxsize=8)
            def factory(n):
                return n
            return factory
    """)
    assert codes(out) == ["GC01"]


def test_gc01_factory_returning_closure_clean(tmp_path):
    # the repo's _make_step idiom: jit closure escapes via return
    out = check_src(tmp_path, """
        import jax
        class Trainer:
            def _make_step(self):
                lam = 0.1
                @jax.jit
                def step(w, x):
                    return w - lam * x
                return step
    """)
    assert out == []


def test_gc01_memoized_factory_with_warmup_call_clean(tmp_path):
    # lru_cache factory may warm the closure before returning it
    out = check_src(tmp_path, """
        import jax
        from functools import lru_cache
        @lru_cache(maxsize=64)
        def _step_cached(dims):
            f = jax.jit(lambda w: w * dims)
            f(0.0)
            return f
    """)
    assert out == []


def test_gc01_self_store_clean(tmp_path):
    out = check_src(tmp_path, """
        import jax
        class Engine:
            def __init__(self, f):
                self._scorer = jax.jit(f)
    """)
    assert out == []


def test_gc01_known_good_compile_factories_pass(extended_scan):
    """The known-good compile-factory population — every lru_cache/jit
    site across models/, ops/ and parallel/ — must pass GC01 clean, and
    the site count proves the assertion is not vacuous."""
    assert [f for f in extended_scan if f.code == "GC01"
            and f.path.startswith(("hivemall_tpu/models/",
                                   "hivemall_tpu/ops/",
                                   "hivemall_tpu/parallel/"))] == []
    n_sites = 0
    for d in ("models", "ops", "parallel"):
        base = os.path.join(PKG, d)
        for fname in os.listdir(base):
            if fname.endswith(".py"):
                with open(os.path.join(base, fname)) as f:
                    src = f.read()
                n_sites += src.count("jax.jit") + src.count("lru_cache(")
    assert n_sites >= 60, f"factory population shrank? saw {n_sites}"


# -- GC02 clock-discipline --------------------------------------------------

def test_gc02_direct_subtraction_flagged(tmp_path):
    out = check_src(tmp_path, """
        import time
        def age(t0):
            return time.time() - t0
    """)
    assert codes(out) == ["GC02"]


def test_gc02_deadline_compare_flagged(tmp_path):
    out = check_src(tmp_path, """
        import time
        def wait(seconds):
            deadline = time.time() + seconds
            while time.time() < deadline:
                pass
    """)
    assert codes(out) == ["GC02"]


def test_gc02_tainted_name_flagged(tmp_path):
    out = check_src(tmp_path, """
        import time
        def span(t_hi):
            now = time.time()
            return now - t_hi
    """)
    assert codes(out) == ["GC02"]


def test_gc02_wall_anchor_export_clean(tmp_path):
    # plain timestamping (no duration math) is the legitimate use
    out = check_src(tmp_path, """
        import time
        def record():
            return {"ts": round(time.time(), 3)}
    """)
    assert out == []


def test_gc02_monotonic_clean(tmp_path):
    out = check_src(tmp_path, """
        import time
        def wait(seconds):
            deadline = time.monotonic() + seconds
            while time.monotonic() < deadline:
                pass
    """)
    assert out == []


def test_gc02_suppression_trailing_and_line_above(tmp_path):
    out = check_src(tmp_path, """
        import time
        def age(mtime, other):
            a = time.time() - mtime  # graftcheck: disable=GC02
            # graftcheck: disable=GC02
            b = time.time() - other
            return a + b
    """)
    assert out == []


# -- GC03 atomic-write ------------------------------------------------------

GC03_BAD = """
    def save_pointer(path, obj):
        with open(path, "w") as f:
            f.write(obj)
"""

GC03_GOOD = """
    import os
    def save_pointer(path, obj):
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            f.write(obj)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
"""


def test_gc03_bare_write_in_io_flagged(tmp_path):
    assert codes(check_src(tmp_path, GC03_BAD, "pkg/io/x.py")) == ["GC03"]
    assert codes(check_src(tmp_path, GC03_BAD, "pkg/serve/x.py")) \
        == ["GC03"]


def test_gc03_atomic_idiom_clean(tmp_path):
    assert check_src(tmp_path, GC03_GOOD, "pkg/io/x.py") == []


def test_gc03_outside_io_serve_not_scanned(tmp_path):
    assert check_src(tmp_path, GC03_BAD, "pkg/models/x.py") == []


def test_gc03_read_open_clean(tmp_path):
    out = check_src(tmp_path, """
        def load(path):
            with open(path) as f:
                return f.read()
    """, "pkg/io/x.py")
    assert out == []


def test_gc03_repo_atomic_helpers_pass():
    for rel in ("io/checkpoint.py", "io/shard_cache.py"):
        out = scan_file(os.path.join(PKG, rel), root=REPO)
        assert [f for f in out if f.code == "GC03"] == [], rel


# -- GC04 lock-discipline ---------------------------------------------------

GC04_RACY = """
    import threading
    class W:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0
            threading.Thread(target=self._a).start()
            threading.Thread(target=self._b).start()
        def _a(self):
            self.n += 1
        def _b(self):
            self.n -= 1
"""


def test_gc04_two_entry_unguarded_flagged(tmp_path):
    out = check_src(tmp_path, GC04_RACY)
    assert codes(out) == ["GC04"] and len(out) == 2


def test_gc04_guarded_writes_clean(tmp_path):
    out = check_src(tmp_path, GC04_RACY.replace(
        "self.n += 1", "with self._lock:\n                self.n += 1")
        .replace("self.n -= 1",
                 "with self._lock:\n                self.n -= 1"))
    assert out == []


def test_gc04_single_entry_clean(tmp_path):
    out = check_src(tmp_path, """
        import threading
        class W:
            def __init__(self):
                threading.Thread(target=self._a).start()
            def _a(self):
                self.n = 1
            def stop(self):
                self.done = True
    """)
    assert out == []


def test_gc04_acquire_without_with_flagged(tmp_path):
    out = check_src(tmp_path, """
        def f(lock):
            lock.acquire()
            try:
                pass
            finally:
                lock.release()
    """)
    assert codes(out) == ["GC04"]


def test_gc04_with_lock_clean(tmp_path):
    out = check_src(tmp_path, """
        def f(lock):
            with lock:
                pass
    """)
    assert out == []


# -- GC05 surface-parity ----------------------------------------------------

def test_gc05_live_extra_key_flagged(tmp_path):
    out = check_src(tmp_path, """
        FOO_STUB = {"ok": 0}
        class P:
            def obs_section(self):
                return {"ok": 0, "extra": 1}
            def _register_obs(self):
                def p():
                    return (self.obs_section() if self else
                            dict(FOO_STUB))
                registry.register("foo", p)
    """)
    assert codes(out) == ["GC05"]
    assert any("extra" in f.message for f in out)


def test_gc05_stub_key_never_emitted_flagged(tmp_path):
    out = check_src(tmp_path, """
        FOO_STUB = {"ok": 0, "ghost": 0}
        class P:
            def obs_section(self):
                return {"ok": 0}
            def _register_obs(self):
                def p():
                    return (self.obs_section() if self else
                            dict(FOO_STUB))
    """)
    assert any("ghost" in f.message for f in out if f.code == "GC05")


def test_gc05_matching_and_dynamic_clean(tmp_path):
    out = check_src(tmp_path, """
        FOO_STUB = {"ok": 0, "n": 0}
        class P:
            def gather(self):
                return {}
            def obs_section(self):
                d = {"ok": 1, "n": 2}
                d.update(self.gather())
                return d
            def _register_obs(self):
                def p():
                    return (self.obs_section() if self else
                            dict(FOO_STUB))
    """)
    assert out == []


def test_gc05_name_grammar_flagged(tmp_path):
    out = check_src(tmp_path, """
        BAR_STUB = {"bad-dash": 0}
        registry.register("bad.name", lambda: {})
    """)
    msgs = [f.message for f in out if f.code == "GC05"]
    assert len(msgs) == 2
    assert any("bad.name" in m for m in msgs)
    assert any("bad-dash" in m for m in msgs)


def test_gc05_repo_stub_parity_clean(extended_scan):
    """The real registry stubs vs their live providers, from source."""
    assert [f for f in extended_scan if f.code == "GC05"] == []


# -- GC06 broad-except ------------------------------------------------------

def test_gc06_unannotated_flagged(tmp_path):
    out = check_src(tmp_path, """
        def f():
            try:
                pass
            except Exception:
                pass
    """, "pkg/serve/x.py")
    assert codes(out) == ["GC06"]


def test_gc06_annotated_clean(tmp_path):
    out = check_src(tmp_path, """
        def f():
            try:
                pass
            except Exception:   # isolation: obs must never kill serving
                pass
            try:
                pass
            except Exception:
                pass            # second style: comment on the body line
    """, "pkg/obs/x.py")
    assert out == []


def test_gc06_outside_hot_dirs_clean(tmp_path):
    out = check_src(tmp_path, """
        def f():
            try:
                pass
            except Exception:
                pass
    """, "pkg/models/x.py")
    assert out == []


# -- whole-repo gate + baseline + self-lint ---------------------------------

def test_repo_gates_clean_with_empty_baseline(extended_scan):
    """The acceptance bar: the package carries ZERO findings — no
    baseline debt at all (docs/STATIC_ANALYSIS.md records the
    contract)."""
    out = [f for f in extended_scan
           if f.path.startswith("hivemall_tpu/")]
    assert out == [], "\n".join(f.render() for f in out)


def test_self_lint():
    out = run_paths([os.path.join(PKG, "tools")], root=REPO)
    assert out == [], "\n".join(f.render() for f in out)


def test_baseline_roundtrip_and_stale_detection(tmp_path):
    findings = check_src(tmp_path, GC03_BAD, "pkg/io/x.py")
    assert findings
    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), findings)
    fresh, stale = gate(findings, load_baseline(str(bl)))
    assert fresh == [] and stale == []
    # the violation gets fixed -> its entry must go stale (gate fails)
    fresh, stale = gate([], load_baseline(str(bl)))
    assert fresh == [] and len(stale) == len(findings)


def test_baseline_stale_scoped_to_scanned_paths(tmp_path):
    """A PARTIAL scan must not flag baseline entries for files outside
    the scanned roots as stale; entries under a scanned root (e.g. a
    deleted file) still go stale."""
    findings = check_src(tmp_path, GC03_BAD, "pkg/io/x.py")
    other = "pkg/serve/other.py::GC03::f::bare open elsewhere"
    gone = "pkg/io/gone.py::GC03::f::file was deleted"
    baseline = [f.fingerprint for f in findings] + [other, gone]
    # scanning only pkg/io: `other` (serve/) is out of scope, `gone`
    # (io/, no longer present) is stale
    fresh, stale = gate(findings, baseline, covered=["pkg/io"])
    assert fresh == [] and stale == [gone]
    # a full scan judges everything
    fresh, stale = gate(findings, baseline, covered=["pkg"])
    assert sorted(stale) == sorted([other, gone])


def test_slo_explicit_wall_ts_vs_default_evaluate():
    """Samples fed with explicit wall-clock ts + evaluate() on the
    default clock: the epoch-mismatch guard anchors the window to the
    freshest sample instead of degrading windows to lifetime totals."""
    import time as _time

    from hivemall_tpu.obs.slo import SloEngine
    eng = SloEngine(p99_ms=100.0, availability=0.999)
    t0 = _time.time()                  # wall epoch, ~1.7e9
    for i in range(6):
        eng.sample({"requests": 100 * (i + 1)}, ts=t0 + 400.0 * i)
    out = eng.evaluate()               # default (monotonic) clock
    w5 = out["windows"]["5m"]
    # the 5m window must anchor at the newest sample and reach only the
    # 400s-older neighbor — NOT the 2000s-old first sample
    assert w5["requests"] == 100, w5
    assert out["windows"]["1h"]["requests"] == 500


def test_baseline_fingerprint_line_insensitive(tmp_path):
    a = check_src(tmp_path, GC03_BAD, "pkg/io/a.py")
    b = check_src(tmp_path, "\n\n# moved two lines down\n"
                  + textwrap.dedent(GC03_BAD), "pkg/io/a.py")
    assert [f.fingerprint for f in a] == [f.fingerprint for f in b]
    assert a[0].line != b[0].line


@pytest.mark.parametrize("mode", ["violation", "baselined", "stale"])
def test_cli_exit_codes(tmp_path, mode):
    tree = tmp_path / "pkg" / "io"
    tree.mkdir(parents=True)
    bad = tree / "bad.py"
    bad.write_text(textwrap.dedent(GC03_BAD))
    bl = tmp_path / "bl.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def run(*extra):
        return subprocess.run(
            [sys.executable, "-m", "hivemall_tpu.tools.graftcheck",
             str(tmp_path / "pkg"), "--root", str(tmp_path), *extra],
            capture_output=True, text=True, cwd=REPO, env=env)

    if mode == "violation":
        r = run()
        assert r.returncode == 1 and "GC03" in r.stdout
    elif mode == "baselined":
        assert run("--write-baseline", str(bl)).returncode == 0
        r = run("--baseline", str(bl))
        assert r.returncode == 0 and "clean" in r.stdout
    else:
        assert run("--write-baseline", str(bl)).returncode == 0
        data = json.loads(bl.read_text())
        data["findings"].append(
            "pkg/io/gone.py::GC03::save::already fixed")
        bl.write_text(json.dumps(data))
        r = run("--baseline", str(bl))
        assert r.returncode == 1 and "STALE" in r.stdout


@pytest.mark.slow
def test_cli_selfcheck():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "hivemall_tpu.tools.graftcheck",
         "--selfcheck"], capture_output=True, text=True, cwd=REPO, env=env)
    assert r.returncode == 0, r.stderr
    assert "bidirectional" in r.stdout


# ===========================================================================
# graftcheck v2: interprocedural dataflow, GC07/GC08, cache, --fix
# ===========================================================================

def check_srcs(tmp_path, files, cache=None):
    """Write a multi-module scratch tree and scan it (the
    interprocedural fixtures need more than one file)."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return run_paths([str(tmp_path)], root=str(tmp_path), cache=cache)


# -- interprocedural non-vacuity: each fixture is INVISIBLE to the PR 11
# intra-module analysis (the single-module scan is pinned clean) and
# MUST be caught once the summaries connect the modules ------------------

GC02_HELPER = """
    import time
    def now_s():
        return time.time()
"""

GC02_USER = """
    from pkg.utils.clockutil import now_s
    def wait(seconds):
        deadline = now_s() + seconds
        while now_s() < deadline:
            pass
"""


def test_gc02_cross_module_taint_flagged(tmp_path):
    out = check_srcs(tmp_path, {"pkg/utils/clockutil.py": GC02_HELPER,
                                "pkg/io/dl.py": GC02_USER})
    hits = [f for f in out if f.code == "GC02"]
    assert hits and hits[0].path == "pkg/io/dl.py"
    assert "now_s" in hits[0].message


def test_gc02_cross_module_missed_by_single_module_scan(tmp_path):
    """The PR 11 miss, pinned: without the helper module in the scan the
    taint trail dies at the function boundary."""
    out = check_srcs(tmp_path, {"pkg/io/dl.py": GC02_USER})
    assert [f for f in out if f.code == "GC02"] == []


def test_gc02_transitive_helper_chain(tmp_path):
    """Taint survives TWO function boundaries (helper returning a
    helper's return)."""
    out = check_srcs(tmp_path, {
        "pkg/utils/clockutil.py": GC02_HELPER,
        "pkg/utils/indirect.py": """
            from pkg.utils.clockutil import now_s
            def stamp():
                return now_s()
        """,
        "pkg/io/dl.py": """
            from pkg.utils.indirect import stamp
            def age(t0):
                return stamp() - t0
        """})
    assert [f.path for f in out if f.code == "GC02"] == ["pkg/io/dl.py"]


GC01_FACTORY = """
    import jax
    def make_step(f):
        return jax.jit(f)
"""


def test_gc01_cross_module_factory_in_loop(tmp_path):
    out = check_srcs(tmp_path, {
        "pkg/ops/fac.py": GC01_FACTORY,
        "pkg/models/use.py": """
            from pkg.ops.fac import make_step
            def score_all(fns, x):
                return [make_step(f)(x) for f in fns]
        """})
    hits = [f for f in out if f.code == "GC01"]
    assert hits and hits[0].path == "pkg/models/use.py"
    assert "make_step" in hits[0].message


def test_gc01_cross_module_missed_by_single_module_scan(tmp_path):
    out = check_srcs(tmp_path, {"pkg/models/use.py": """
        from pkg.ops.fac import make_step
        def score_all(fns, x):
            return [make_step(f)(x) for f in fns]
    """})
    assert [f for f in out if f.code == "GC01"] == []


def test_gc01_factory_product_escapes_clean(tmp_path):
    """Callers that STORE the factory product (the repo's _make_step
    idiom) must stay clean — only loop/immediate-invoke calls fire."""
    out = check_srcs(tmp_path, {
        "pkg/ops/fac.py": GC01_FACTORY,
        "pkg/models/use.py": """
            from pkg.ops.fac import make_step
            class T:
                def __init__(self, f):
                    self._step = make_step(f)
        """})
    assert out == []


def test_gc01_memoized_factory_calls_clean(tmp_path):
    """A memoized factory returns the SAME closure per config — calling
    it per step (even in a loop) is a cache hit, never a recompile."""
    out = check_srcs(tmp_path, {
        "pkg/ops/fac.py": """
            import jax
            from functools import lru_cache
            @lru_cache(maxsize=8)
            def make_step(n):
                return jax.jit(lambda v: v * n)
        """,
        "pkg/models/use.py": """
            from pkg.ops.fac import make_step
            def score_all(xs):
                return [make_step(8)(x) for x in xs]
        """})
    assert out == []


GC04_CROSS = """
    import threading
    from pkg.serve.helper import bump_counter
    class X:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            threading.Thread(target=self._a).start()
            threading.Thread(target=self._b).start()
        def _a(self):
            bump_counter(self)
        def _b(self):
            with self._lock:
                self.count -= 1
"""


def test_gc04_cross_module_param_write_flagged(tmp_path):
    out = check_srcs(tmp_path, {
        "pkg/serve/helper.py": "def bump_counter(obj):\n"
                               "    obj.count += 1\n",
        "pkg/serve/w.py": GC04_CROSS})
    hits = [f for f in out if f.code == "GC04"]
    assert hits and any("via bump_counter" in f.message for f in hits)


def test_gc04_cross_module_missed_by_single_module_scan(tmp_path):
    out = check_srcs(tmp_path, {"pkg/serve/w.py": GC04_CROSS})
    assert [f for f in out if f.code == "GC04"] == []


def test_gc04_write_via_method_chain_flagged(tmp_path):
    """A write buried one method call below the thread entry — invisible
    to the PR 11 entry-local walk."""
    out = check_srcs(tmp_path, {"pkg/serve/w.py": """
        import threading
        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0
                threading.Thread(target=self._a).start()
                threading.Thread(target=self._b).start()
            def _a(self):
                self._bump()
            def _bump(self):
                self.n += 1
            def _b(self):
                self.n -= 1
    """})
    hits = [f for f in out if f.code == "GC04"]
    assert any("via self._bump" in f.message for f in hits)


def test_gc04_nested_closure_write_still_flagged(tmp_path):
    """Writes inside a nested helper closure of a summarized thread
    entry: the closure is absent from the entry's summary and a bare
    call to it resolves to None, so the rule must ALSO walk the entry's
    nested defs (regression — the v2 summary path once replaced the
    walk entirely and this PR 11-era catch went silent)."""
    out = check_srcs(tmp_path, {"pkg/serve/w.py": """
        import threading
        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
                threading.Thread(target=self._a).start()
                threading.Thread(target=self._b).start()
            def _a(self):
                def bump():
                    self.count += 1
                for _ in range(10):
                    bump()
            def _b(self):
                with self._lock:
                    self.count = 0
    """})
    hits = [f for f in out if f.code == "GC04" and "count" in f.message]
    assert hits and hits[0].symbol == "W._a"


def test_gc04_lock_held_at_call_site_propagates(tmp_path):
    """A write is guarded when the CALL EDGE held the lock, even though
    the write site itself shows no with-block (the engine.poll() ->
    _load_newest() shape)."""
    out = check_srcs(tmp_path, {"pkg/serve/w.py": """
        import threading
        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0
                threading.Thread(target=self._a).start()
                threading.Thread(target=self._b).start()
            def _a(self):
                with self._lock:
                    self._bump()
            def _bump(self):
                self.n += 1
            def _b(self):
                with self._lock:
                    self.n -= 1
    """})
    assert [f for f in out if f.code == "GC04"] == []


# -- GC07 transfer-discipline --------------------------------------------

def test_gc07_direct_transfer_in_loop_flagged(tmp_path):
    out = check_src(tmp_path, """
        import numpy as np
        def train(step, batches):
            losses = []
            for b in batches:
                losses.append(float(np.asarray(step(b))))
            return losses
    """, "pkg/models/hot.py")
    assert codes(out) == ["GC07"]


def test_gc07_one_hop_helper_flagged(tmp_path):
    out = check_srcs(tmp_path, {
        "pkg/ops/fetch.py": "import numpy as np\n"
                            "def fetch(x):\n"
                            "    return float(np.asarray(x))\n",
        "pkg/models/hot.py": """
            from pkg.ops.fetch import fetch
            def train(step, batches):
                return [fetch(step(b)) for b in batches]
        """})
    hits = [f for f in out if f.code == "GC07"]
    assert hits and hits[0].path == "pkg/models/hot.py"
    assert "fetch" in hits[0].message


def test_gc07_transfer_outside_loop_clean(tmp_path):
    out = check_src(tmp_path, """
        import numpy as np
        def train(step, batches):
            acc = None
            for b in batches:
                acc = step(b, acc)
            return float(np.asarray(acc))
    """, "pkg/models/hot.py")
    assert out == []


def test_gc07_outside_models_ops_not_scanned(tmp_path):
    out = check_src(tmp_path, """
        import numpy as np
        def drain(batches):
            return [np.asarray(b) for b in batches]
    """, "pkg/io/x.py")
    assert out == []


def test_gc07_loop_iter_expression_clean(tmp_path):
    """The iterable evaluates ONCE — np.asarray in the for-iter position
    is not a per-iteration sync."""
    out = check_src(tmp_path, """
        import numpy as np
        def walk(xs):
            total = 0
            for v in np.asarray(xs):
                total += v
            return total
    """, "pkg/models/x.py")
    assert out == []


def test_gc07_block_until_ready_flagged(tmp_path):
    out = check_src(tmp_path, """
        def train(step, batches):
            for b in batches:
                step(b).block_until_ready()
    """, "pkg/ops/x.py")
    assert codes(out) == ["GC07"]


# -- GC08 thread-lifecycle -----------------------------------------------

GC08_LEAKY = """
    import threading
    class Daemon:
        def start(self):
            self._t = threading.Thread(target=self._run, daemon=True)
            self._t.start()
        def _run(self):
            while True:
                pass
"""


def test_gc08_unjoined_looping_thread_flagged(tmp_path):
    out = check_src(tmp_path, GC08_LEAKY)
    assert codes(out) == ["GC08"]


def test_gc08_joined_thread_clean(tmp_path):
    out = check_src(tmp_path, """
        import threading
        class Daemon:
            def start(self):
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()
            def _run(self):
                while True:
                    pass
            def close(self):
                self._t.join(timeout=5)
    """)
    assert out == []


def test_gc08_poison_pill_event_clean(tmp_path):
    out = check_src(tmp_path, """
        import threading
        class Daemon:
            def start(self):
                self._stop = threading.Event()
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()
            def _run(self):
                while not self._stop.wait(1.0):
                    pass
            def close(self):
                self._stop.set()
    """)
    assert out == []


def test_gc08_event_gate_never_set_flagged(tmp_path):
    """A loop gated on an Event nothing ever set()s is NOT a shutdown
    path — the finding names the dangling gate."""
    out = check_src(tmp_path, """
        import threading
        class Daemon:
            def start(self):
                self._stop = threading.Event()
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()
            def _run(self):
                while not self._stop.wait(1.0):
                    pass
    """)
    assert codes(out) == ["GC08"]
    assert "_stop" in out[0].message


def test_gc08_loop_join_over_thread_list_clean(tmp_path):
    """The fleet idiom: threads appended to self._threads, joined in a
    for-loop at stop()."""
    out = check_src(tmp_path, """
        import threading
        class M:
            def start(self):
                self._threads = []
                for name in ("a", "b"):
                    t = threading.Thread(target=self._loop, daemon=True)
                    t.start()
                    self._threads.append(t)
            def _loop(self):
                while True:
                    pass
            def stop(self):
                for t in self._threads:
                    t.join(timeout=5)
    """)
    assert out == []


def test_gc08_run_once_target_clean(tmp_path):
    """No loop in the target: the thread ends on its own — no shutdown
    obligation (the engine's background-warmup shape)."""
    out = check_src(tmp_path, """
        import threading
        class W:
            def start(self):
                self._t = threading.Thread(target=self._work, daemon=True)
                self._t.start()
            def _work(self):
                x = 1 + 1
                return x
    """)
    assert out == []


def test_gc08_anonymous_local_thread_out_of_scope(tmp_path):
    """Fire-and-forget threads never stored on self (per-connection
    handlers, locally-joined workers) are out of GC08's scope."""
    out = check_src(tmp_path, """
        import threading
        class A:
            def handle(self, conns):
                for c in conns:
                    threading.Thread(target=self._serve,
                                     args=(c,), daemon=True).start()
            def _serve(self, c):
                while c.alive():
                    pass
    """)
    assert out == []


# -- pass-1 robustness: exotic constructs degrade, never crash ------------

def test_pass1_decorated_async_lambda_property_no_crash(tmp_path):
    """Decorated defs, async defs, lambdas as thread targets and
    properties must all survive pass 1; unresolvable constructs degrade
    to 'unknown' (no findings invented)."""
    out = check_srcs(tmp_path, {"pkg/serve/exotic.py": """
        import threading
        import functools

        def mystery(fn):
            @functools.wraps(fn)
            def inner(*a, **k):
                return fn(*a, **k)
        　
        class E:
            def __init__(self):
                self._t = threading.Thread(target=lambda: self._spin())
                self._t.start()

            @property
            def size(self):
                return 1

            @size.setter
            def size(self, v):
                self._size = v

            @mystery
            def decorated(self):
                return self.size

            async def poll(self):
                return self.size

            def _spin(self):
                while True:
                    pass

            def close(self):
                self._t.join(timeout=1)
    """.replace("　", "")})
    assert [f for f in out if f.code == "GC00"] == []
    # the lambda target resolves through to _spin or degrades silently;
    # either way the joined thread must not produce a GC08 finding
    assert [f for f in out if f.code == "GC08"] == []


def test_pass1_lambda_thread_target_degrades_unknown(tmp_path):
    """A lambda target that cannot be resolved produces NO GC08 finding
    even without a join — unknown degrades to silence, not certainty."""
    out = check_src(tmp_path, """
        import threading
        class E:
            def start(self, job):
                self._t = threading.Thread(target=lambda: job.run())
                self._t.start()
    """)
    assert [f for f in out if f.code == "GC08"] == []


def test_summaries_degrade_on_dynamic_dispatch(tmp_path):
    """getattr dispatch is unresolvable: no GC02 finding is invented for
    a helper the analysis cannot identify."""
    out = check_src(tmp_path, """
        import time
        def get_clock(name):
            return getattr(time, name)
        def wait(seconds):
            clock = get_clock("monotonic")
            deadline = clock() + seconds
            while clock() < deadline:
                pass
    """)
    assert out == []


# -- findings cache -------------------------------------------------------

def test_cache_warm_replay_identical(tmp_path):
    cache = str(tmp_path / "cache.json")
    files = {"pkg/io/bad.py": GC03_BAD}
    cold = check_srcs(tmp_path, files, cache=cache)
    assert cold and os.path.exists(cache)
    warm = run_paths([str(tmp_path)], root=str(tmp_path), cache=cache)
    assert [f.fingerprint for f in warm] == [f.fingerprint for f in cold]
    assert [(f.line, f.col) for f in warm] == [(f.line, f.col)
                                              for f in cold]


def test_cache_invalidated_by_edit(tmp_path):
    cache = str(tmp_path / "cache.json")
    check_srcs(tmp_path, {"pkg/io/bad.py": GC03_BAD}, cache=cache)
    # fix the violation on disk: the cached findings must NOT be replayed
    (tmp_path / "pkg" / "io" / "bad.py").write_text(
        textwrap.dedent(GC03_GOOD))
    out = run_paths([str(tmp_path)], root=str(tmp_path), cache=cache)
    assert out == []


def test_cache_invalidated_by_rulestamp(tmp_path):
    from hivemall_tpu.tools.graftcheck.engine import _cache_load
    cache = str(tmp_path / "cache.json")
    check_srcs(tmp_path, {"pkg/io/bad.py": GC03_BAD}, cache=cache)
    data = json.loads((tmp_path / "cache.json").read_text())
    data["stamp"] = "graftcheck-v0-ancient"
    (tmp_path / "cache.json").write_text(json.dumps(data))
    shas = {rel: e["sha"] for rel, e in data["files"].items()}
    assert _cache_load(cache, shas) is None


def test_cache_invalidated_by_new_file(tmp_path):
    """Interprocedural coupling: ADDING a module must invalidate the
    whole cache (its summaries can change other files' findings)."""
    cache = str(tmp_path / "cache.json")
    check_srcs(tmp_path, {"pkg/io/dl.py": GC02_USER}, cache=cache)
    out = check_srcs(tmp_path, {"pkg/io/dl.py": GC02_USER,
                                "pkg/utils/clockutil.py": GC02_HELPER},
                     cache=cache)
    assert [f for f in out if f.code == "GC02"]


# -- --fix ---------------------------------------------------------------

def test_fix_gc02_rewrites_clock_and_taint_sources(tmp_path):
    tree = tmp_path / "pkg"
    tree.mkdir()
    bad = tree / "clockbad.py"
    bad.write_text(textwrap.dedent("""
        import time
        def wait(s):
            deadline = time.time() + s
            while time.time() < deadline:
                pass
    """))
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def run(*extra):
        return subprocess.run(
            [sys.executable, "-m", "hivemall_tpu.tools.graftcheck",
             str(tree), "--root", str(tmp_path), *extra],
            capture_output=True, text=True, cwd=REPO, env=env)

    r = run("--fix")
    assert r.returncode == 1
    assert "-    deadline = time.time() + s" in r.stdout
    assert "+    deadline = time.monotonic() + s" in r.stdout
    assert bad.read_text().count("time.time()") == 2  # diff only
    r = run("--fix", "--write")
    assert r.returncode == 0, r.stderr
    assert "time.time()" not in bad.read_text()
    assert run().returncode == 0          # post-fix scan gates clean


def test_fix_gc06_inserts_annotation(tmp_path):
    tree = tmp_path / "pkg" / "serve"
    tree.mkdir(parents=True)
    bad = tree / "x.py"
    bad.write_text(textwrap.dedent("""
        def f():
            try:
                pass
            except Exception:
                pass
    """))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "hivemall_tpu.tools.graftcheck",
         str(tmp_path / "pkg"), "--root", str(tmp_path),
         "--fix", "--write"],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert r.returncode == 0, r.stderr
    assert "except Exception:  #" in bad.read_text()


# -- repo-level: the EXTENDED default scan gates clean --------------------

def test_extended_repo_surface_gates_clean(extended_scan):
    """tests/ and the graft entry obey the same invariants as
    the package (the PR 12 scan-coverage satellite): the full default
    surface carries ZERO findings."""
    assert extended_scan == [], "\n".join(
        f.render() for f in extended_scan)


# -- review-pass regressions ----------------------------------------------

def test_fix_helper_tainted_gc02_not_claimed_fixable(tmp_path):
    """A GC02 finding whose taint source is a HELPER return carries no
    literal time.time() to rewrite: --fix --write must not report
    success on a no-op (the gate would still fail next run)."""
    files = {"pkg/utils/clockutil.py": GC02_HELPER,
             "pkg/io/dl.py": """
                 from pkg.utils.clockutil import now_s
                 def over(limit):
                     t0 = now_s()
                     return limit - t0 > 5
             """}
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    out = run_paths([str(tmp_path)], root=str(tmp_path))
    hits = [f for f in out if f.code == "GC02"]
    assert hits and all(f.fix_kind is None for f in hits)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    before = (tmp_path / "pkg" / "io" / "dl.py").read_text()
    r = subprocess.run(
        [sys.executable, "-m", "hivemall_tpu.tools.graftcheck",
         str(tmp_path / "pkg"), "--root", str(tmp_path),
         "--fix", "--write"],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert "rewrote 0 finding(s)" in r.stderr, r.stderr
    assert (tmp_path / "pkg" / "io" / "dl.py").read_text() == before


def test_dotted_module_alias_resolution(tmp_path):
    """`import pkg.utils as utils` + `utils.clockutil.now_s()` must
    resolve through the alias even when the alias equals the target's
    last component (the review-caught resolution bug)."""
    out = check_srcs(tmp_path, {
        "pkg/utils/clockutil.py": GC02_HELPER,
        "pkg/utils/__init__.py": "",
        "pkg/__init__.py": "",
        "pkg/io/dl.py": """
            import pkg.utils as utils
            def wait(seconds):
                deadline = utils.clockutil.now_s() + seconds
                while utils.clockutil.now_s() < deadline:
                    pass
        """})
    hits = [f for f in out if f.code == "GC02"]
    assert hits and hits[0].path == "pkg/io/dl.py", \
        "\n".join(f.render() for f in out)


def test_package_reexport_hop_resolves(tmp_path):
    """`from .clockutil import now_s` inside pkg/utils/__init__.py is a
    PACKAGE-relative import: consumers importing through the package
    re-export must still carry the taint (review-caught: packages
    resolved one level too high and the hop silently went dark)."""
    out = check_srcs(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/utils/clockutil.py": GC02_HELPER,
        "pkg/utils/__init__.py": "from .clockutil import now_s\n",
        "pkg/io/dl.py": """
            from pkg.utils import now_s
            def wait(seconds):
                deadline = now_s() + seconds
                while now_s() < deadline:
                    pass
        """})
    hits = [f for f in out if f.code == "GC02"]
    assert hits and hits[0].path == "pkg/io/dl.py", \
        "\n".join(f.render() for f in out)


def test_fix_rewrites_every_taint_source_line(tmp_path):
    """A name assigned from time.time() on SEVERAL lines: --fix --write
    must rewrite all of them so the rescan gates clean (review-caught:
    only the last-seen assignment line was recorded)."""
    tree = tmp_path / "pkg"
    tree.mkdir()
    bad = tree / "multi.py"
    bad.write_text(textwrap.dedent("""
        import time
        def span(flag, t1):
            t0 = time.time()
            if flag:
                t0 = time.time()
            return t0 - t1
    """))
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def run(*extra):
        return subprocess.run(
            [sys.executable, "-m", "hivemall_tpu.tools.graftcheck",
             str(tree), "--root", str(tmp_path), *extra],
            capture_output=True, text=True, cwd=REPO, env=env)

    assert run("--fix", "--write").returncode == 0
    assert "time.time()" not in bad.read_text()
    assert run().returncode == 0, "rescan after --fix --write must gate"


def test_fix_gc02_spares_wall_anchor_assignments(tmp_path):
    """A tainted name that ALSO feeds an epoch export (`ts = start *
    1e6`, the chrome-trace anchor pattern) must not be claimed fixable:
    rewriting its assignment would corrupt the anchor, and rewriting
    just the arithmetic would mix clocks (review-caught — --fix --write
    silently monotonic-ized wall anchors)."""
    out = check_srcs(tmp_path, {"pkg/io/dl.py": """
        import time
        def dual():
            start = time.time()
            ts_epoch_us = start * 1e6
            dur = time.time() - start
            return ts_epoch_us, dur
    """})
    hits = [f for f in out if f.code == "GC02"]
    assert hits, "dual-use anchor arithmetic must still be FLAGGED"
    assert all(f.fix_kind is None and not f.fix_lines for f in hits), \
        [f.to_json() for f in hits]


def test_cache_mangled_entry_rescans(tmp_path):
    """A cache whose per-file entry is not a dict (hand-edit / merge
    damage) must degrade to a full re-scan, never crash the gate
    (review-caught AttributeError)."""
    from hivemall_tpu.tools.graftcheck.engine import _cache_load
    from hivemall_tpu.tools.graftcheck.rules import RULESTAMP
    cache = tmp_path / "cache.json"
    cache.write_text(json.dumps({"stamp": RULESTAMP,
                                 "files": {"a.py": "xyz"}}))
    assert _cache_load(str(cache), {"a.py": "xyz"}) is None
    # and end-to-end: a scan handed the mangled cache still completes
    out = check_srcs(tmp_path, {"pkg/io/bad.py": GC03_BAD},
                     cache=str(cache))
    assert [f for f in out if f.code == "GC03"]


def test_tsan_env_negatives_stay_disabled(monkeypatch):
    from hivemall_tpu.testing import tsan
    for v in ("0", "false", "False", "NO", "off", ""):
        monkeypatch.setenv(tsan.ENV_FLAG, v)
        if not tsan.enabled():
            assert tsan.maybe_enable() is False, v


# =========================================================================
# v3 (PR 14): GC09-GC12 — XLA compile contract + resource lifecycle
# =========================================================================

# -- GC09 tracer-safety ----------------------------------------------------

def test_gc09_np_cast_and_branch_flagged(tmp_path):
    out = check_src(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def step(w, g):
            lr = float(np.mean(g))
            if g > 0:
                w = w - lr * g
            return w
    """)
    hits = [f for f in out if f.code == "GC09"]
    msgs = " | ".join(f.message for f in hits)
    assert "np.mean" in msgs            # the numpy concretization
    assert "float" in msgs              # the cast
    assert "control flow" in msgs       # the Python branch
    # the np call is the mechanical --fix subset
    assert any(f.fix_kind == "gc09-jnp" for f in hits)


def test_gc09_item_tolist_flagged(tmp_path):
    out = check_src(tmp_path, """
        import jax

        @jax.jit
        def fetch(x):
            return x.sum().item()
    """)
    hits = [f for f in out if f.code == "GC09"]
    assert hits and ".item()" in hits[0].message


def test_gc09_concrete_attrs_and_is_none_clean(tmp_path):
    """shape/dtype reads and `is None` checks are static under trace —
    the repo's cores lean on both (val-None elision, B = shape[1])."""
    out = check_src(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def core(w, idx, val):
            B = idx.shape[0]
            if val is None:
                val = (idx != 0).astype(jnp.float32)
            return (w[idx] * val).sum() / B
    """)
    assert [f for f in out if f.code == "GC09"] == []


def test_gc09_static_argnums_params_clean(tmp_path):
    """A static_argnums position is concrete — branching on it is the
    POINT of marking it static."""
    out = check_src(tmp_path, """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnums=(1,))
        def step(w, mode):
            if mode == "train":
                return w * 2.0
            return w
    """)
    assert [f for f in out if f.code == "GC09"] == []


def test_gc09_lax_scan_body_params_traced(tmp_path):
    out = check_src(tmp_path, """
        import jax
        import numpy as np

        def run(xs, w0):
            def body(carry, x):
                bad = np.sum(x)
                return carry + bad, bad
            return jax.lax.scan(body, w0, xs)
    """)
    hits = [f for f in out if f.code == "GC09"]
    assert hits and "np.sum" in hits[0].message


GC09_HELPER = """
    import numpy as np

    def host_norm(v):
        return np.sum(v * v)
"""

GC09_JIT_USER = """
    import jax
    from pkg.ops.helper_np import host_norm

    @jax.jit
    def fused(x):
        return host_norm(x * 2.0)
"""


def test_gc09_cross_module_taint_flagged(tmp_path):
    """The np call lives in a helper module; it is only a hazard
    because a jit body in ANOTHER module hands it a tracer."""
    out = check_srcs(tmp_path, {"pkg/ops/helper_np.py": GC09_HELPER,
                                "pkg/models/user.py": GC09_JIT_USER})
    hits = [f for f in out if f.code == "GC09"]
    assert hits and hits[0].path == "pkg/ops/helper_np.py"
    assert "host_norm" in hits[0].message


def test_gc09_cross_module_missed_by_single_module_scan(tmp_path):
    """Without the jit caller in the scan, the helper is just host-side
    numpy — no tracer ever reaches it."""
    out = check_srcs(tmp_path, {"pkg/ops/helper_np.py": GC09_HELPER})
    assert [f for f in out if f.code == "GC09"] == []


def test_gc09_untraced_host_helper_clean(tmp_path):
    """The same helper called from plain host code stays clean — GC09
    is about TRACED reachability, not numpy style."""
    out = check_srcs(tmp_path, {
        "pkg/ops/helper_np.py": GC09_HELPER,
        "pkg/models/host.py": """
            from pkg.ops.helper_np import host_norm
            def evaluate(rows):
                return [host_norm(r) for r in rows]
        """})
    assert [f for f in out if f.code == "GC09"] == []


def test_gc09_suppression_honored(tmp_path):
    out = check_src(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def step(w):
            return np.asarray(w)  # graftcheck: disable=GC09,GC07
    """)
    assert [f for f in out if f.code == "GC09"] == []


def test_gc09_tests_dir_exempt(tmp_path):
    out = check_src(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def step(w):
            return np.asarray(w)
    """, rel="tests/test_adhoc.py")
    assert [f for f in out if f.code == "GC09"] == []


# -- GC10 carry-stability --------------------------------------------------

def test_gc10_scalar_literal_carry_leaf_flagged(tmp_path):
    out = check_src(tmp_path, """
        import jax

        def run(xs, w):
            def body(carry, x):
                w, t = carry
                return (w + x, 0.0), w
            return jax.lax.scan(body, (w, 0.0), xs)
    """)
    hits = [f for f in out if f.code == "GC10"]
    assert hits and "0.0" in hits[0].message


def test_gc10_astype_literal_dtype_flagged(tmp_path):
    out = check_src(tmp_path, """
        import jax

        def run(xs, s0):
            def body(carry, x):
                s, t = carry
                return (s + x, t.astype('float32')), s
            return jax.lax.scan(body, s0, xs)
    """)
    hits = [f for f in out if f.code == "GC10"]
    assert hits and "astype" in hits[0].message


def test_gc10_astype_of_input_dtype_clean(tmp_path):
    """x.astype(w.dtype) PRESERVES the carry leaf dtype — the linear
    core's w_new.astype(w.dtype) idiom must pass."""
    out = check_src(tmp_path, """
        import jax

        def run(xs, w0):
            def body(carry, x):
                w, t = carry
                w2 = (w + x).astype(w.dtype)
                return (w2, t + 1.0), w2
            return jax.lax.scan(body, w0, xs)
    """)
    assert [f for f in out if f.code == "GC10"] == []


def test_gc10_divergent_return_lengths_flagged(tmp_path):
    out = check_src(tmp_path, """
        import jax

        def run(xs, s0, flag):
            def body(carry, x):
                s, t = carry
                if x.sum() > 0:
                    return (s, t, s), s
                return (s, t), s
            return jax.lax.scan(body, s0, xs)
    """)
    hits = [f for f in out if f.code == "GC10"]
    assert hits and "differ in length" in hits[0].message


GC10_BODY = """
    def body(carry, x):
        s, t = carry
        return (s + x, t.astype('float32')), s
"""


def test_gc10_cross_module_scan_body_flagged(tmp_path):
    """The body is only a scan body because ANOTHER module hands it to
    lax.scan — the finding lands in the body's module."""
    out = check_srcs(tmp_path, {
        "pkg/ops/scan_body.py": GC10_BODY,
        "pkg/models/runner.py": """
            import jax
            from pkg.ops.scan_body import body
            def run(xs, s0):
                return jax.lax.scan(body, s0, xs)
        """})
    hits = [f for f in out if f.code == "GC10"]
    assert hits and hits[0].path == "pkg/ops/scan_body.py"


def test_gc10_cross_module_missed_by_single_module_scan(tmp_path):
    out = check_srcs(tmp_path, {"pkg/ops/scan_body.py": GC10_BODY})
    assert [f for f in out if f.code == "GC10"] == []


def test_gc10_repo_scan_bodies_pass_clean(repo_index):
    """Non-vacuity pin: the repo's real scan bodies (ops.scan megastep
    body, trees round_fn, the models/ slab bodies) are IN the analyzed
    population and all pass."""
    idx = repo_index
    ops_bodies = {fid for fid in idx.scan_bodies
                  if fid[0].startswith("hivemall_tpu/")}
    assert len(ops_bodies) >= 5, sorted(ops_bodies)
    assert ("hivemall_tpu/ops/scan.py",
            "make_megastep.megastep.body") in ops_bodies
    assert ("hivemall_tpu/ops/trees.py",
            "boost_loop_xgb.loop.round_fn") in ops_bodies


def _repo_files():
    from hivemall_tpu.tools.graftcheck import engine as eng
    files = {}
    for p in eng.iter_py_files(eng._default_paths()):
        rel = os.path.relpath(os.path.abspath(p), REPO).replace(
            os.sep, "/")
        files[rel] = os.path.abspath(p)
    return files


# -- GC11 donation-discipline ----------------------------------------------

def test_gc11_read_after_donate_flagged(tmp_path):
    out = check_src(tmp_path, """
        import jax

        def core(w, s, x):
            return w + x, s

        def train(w, s, xs):
            step = jax.jit(core, donate_argnums=(0, 1))
            out, s2 = step(w, s)
            return out, s2, w.sum()
    """)
    hits = [f for f in out if f.code == "GC11"]
    assert hits and "'w'" in hits[0].message
    assert "DONATED" in hits[0].message


def test_gc11_rebind_pattern_clean(tmp_path):
    """state = step(state, batch) — the donated name is REBOUND by the
    call's own assignment (the repo's universal dispatch shape)."""
    out = check_src(tmp_path, """
        import jax

        def core(w, s, x):
            return w + x, s

        def train(w, s, xs):
            step = jax.jit(core, donate_argnums=(0, 1))
            for x in xs:
                w, s = step(w, s)
            return w, s
    """)
    assert [f for f in out if f.code == "GC11"] == []


def test_gc11_scannable_without_donation_flagged(tmp_path):
    out = check_src(tmp_path, """
        import jax

        def scannable(step, core):
            step.core = core
            return step

        def make_step():
            def core(w, s, t, idx):
                return w, s, 0.0
            return scannable(jax.jit(core), core)
    """, rel="pkg/ops/mystep.py")
    hits = [f for f in out if f.code == "GC11"]
    assert hits and "donate_argnums" in hits[0].message


def test_gc11_scannable_with_donation_clean(tmp_path):
    out = check_src(tmp_path, """
        import jax
        from functools import partial

        def scannable(step, core):
            step.core = core
            return step

        def make_step():
            def core(w, s, t, idx):
                return w, s, 0.0
            return scannable(
                partial(jax.jit, donate_argnums=(0, 1))(core), core)
    """, rel="pkg/ops/mystep.py")
    assert [f for f in out if f.code == "GC11"] == []


GC11_FACTORY = """
    import jax

    def make_step(core):
        return jax.jit(core, donate_argnums=(0, 1))
"""

GC11_BAD_READER = """
    from pkg.ops.donate_factory import make_step

    def train(core, w, s, xs):
        step = make_step(core)
        w2, s2 = step(w, s)
        return w2, s2, w.sum()
"""


def test_gc11_cross_module_donated_factory_flagged(tmp_path):
    """The donation is declared in the factory's module; the
    read-after-donate happens in the caller's."""
    out = check_srcs(tmp_path, {
        "pkg/ops/donate_factory.py": GC11_FACTORY,
        "pkg/models/reader.py": GC11_BAD_READER})
    hits = [f for f in out if f.code == "GC11"]
    assert hits and hits[0].path == "pkg/models/reader.py"


def test_gc11_cross_module_missed_by_single_module_scan(tmp_path):
    out = check_srcs(tmp_path, {"pkg/models/reader.py": GC11_BAD_READER})
    assert [f for f in out if f.code == "GC11"] == []


def test_gc11_repo_donation_population(repo_index):
    """Non-vacuity pin: the repo's donate_argnums population (the ops/
    scannable cores, make_megastep, the models/ step factories) is in
    the index — at least 4 donated defs and the 3 donating factories,
    each named."""
    idx = repo_index
    donated_defs = [s for s in idx.functions.values()
                    if s.donated_positions]
    factories = [s for s in idx.functions.values() if s.returns_donated]
    assert len(donated_defs) >= 4
    assert len(factories) >= 3
    assert {("hivemall_tpu/ops/scan.py", "make_megastep"),
            ("hivemall_tpu/models/word2vec.py", "_step_cached"),
            ("hivemall_tpu/models/classifier.py",
             "AdaGradRDATrainer._make_rda_step")} <= \
        {s.fid for s in factories}
    # and the traced-parameter closure is populated (GC09 non-vacuity)
    assert len(idx.traced) >= 200


# -- GC12 resource-lifecycle -----------------------------------------------

def test_gc12_never_closed_flagged(tmp_path):
    out = check_src(tmp_path, """
        import socket

        def ping(addr):
            s = socket.create_connection(addr)
            s.sendall(b'x')
            return s.recv(4)
    """, rel="pkg/serve/conn.py")
    hits = [f for f in out if f.code == "GC12"]
    assert hits and "never closed" in hits[0].message


def test_gc12_straight_line_close_flagged(tmp_path):
    out = check_src(tmp_path, """
        import socket

        def probe(addr):
            s = socket.create_connection(addr)
            s.sendall(b'ping')
            data = s.recv(16)
            s.close()
            return data
    """, rel="pkg/serve/conn.py")
    hits = [f for f in out if f.code == "GC12"]
    assert hits and "straight-line" in hits[0].message


def test_gc12_with_and_finally_clean(tmp_path):
    out = check_src(tmp_path, """
        import socket

        def a(addr):
            with socket.create_connection(addr) as s:
                return s.recv(4)

        def b(addr):
            s = socket.create_connection(addr)
            try:
                s.sendall(b'x')
                return s.recv(4)
            finally:
                s.close()
    """, rel="pkg/serve/conn.py")
    assert [f for f in out if f.code == "GC12"] == []


def test_gc12_cleanup_and_reraise_clean(tmp_path):
    """The router _RawConn idiom after the PR 14 fix: close in an
    except handler that re-raises."""
    out = check_src(tmp_path, """
        import socket

        class Conn:
            def __init__(self, addr):
                self.sock = socket.create_connection(addr)
                try:
                    self.sock.setsockopt(1, 1, 1)
                    self.rfile = self.sock.makefile('rb')
                except OSError:
                    self.sock.close()
                    raise

            def close(self):
                self.rfile.close()
                self.sock.close()
    """, rel="pkg/serve/conn.py")
    assert [f for f in out if f.code == "GC12"] == []


def test_gc12_init_store_without_guard_flagged(tmp_path):
    """The pre-fix _RawConn shape: acquire, store on self, then raising
    calls with no close-and-reraise."""
    out = check_src(tmp_path, """
        import socket

        class Conn:
            def __init__(self, addr):
                self.sock = socket.create_connection(addr)
                self.sock.setsockopt(1, 1, 1)
                self.rfile = self.sock.makefile('rb')

            def close(self):
                self.sock.close()
    """, rel="pkg/serve/conn.py")
    hits = [f for f in out if f.code == "GC12"]
    assert hits and "mid-constructor" in hits[0].message


def test_gc12_self_store_with_release_path_clean(tmp_path):
    out = check_src(tmp_path, """
        import socket

        class Server:
            def start(self, addr):
                self._sock = socket.create_connection(addr)

            def stop(self):
                self._sock.close()
    """, rel="pkg/serve/srv.py")
    assert [f for f in out if f.code == "GC12"] == []


def test_gc12_self_store_without_release_flagged(tmp_path):
    out = check_src(tmp_path, """
        import socket

        class Server:
            def start(self, addr):
                self._sock = socket.create_connection(addr)
    """, rel="pkg/serve/srv.py")
    hits = [f for f in out if f.code == "GC12"]
    assert hits and "ever releases" in hits[0].message


def test_gc12_pool_swap_release_credited(tmp_path):
    """The router close_pool idiom: pool, self._pool = self._pool, []
    then loop-close over the swapped local."""
    out = check_src(tmp_path, """
        import socket

        class Pool:
            def grab(self, addr):
                self._live = socket.create_connection(addr)

            def close_all(self):
                live, self._live = self._live, None
                live.close()
    """, rel="pkg/serve/pool.py")
    assert [f for f in out if f.code == "GC12"] == []


def test_gc12_httperror_read_without_close_flagged(tmp_path):
    out = check_src(tmp_path, """
        import json
        import urllib.error
        import urllib.request

        def probe(url):
            try:
                with urllib.request.urlopen(url) as r:
                    return json.loads(r.read())
            except urllib.error.HTTPError as e:
                return json.loads(e.read())
    """, rel="pkg/serve/probe.py")
    hits = [f for f in out if f.code == "GC12"]
    assert hits and "HTTPError" in hits[0].message


def test_gc12_httperror_closed_clean(tmp_path):
    out = check_src(tmp_path, """
        import json
        import urllib.error
        import urllib.request

        def probe(url):
            try:
                with urllib.request.urlopen(url) as r:
                    return json.loads(r.read())
            except urllib.error.HTTPError as e:
                try:
                    return json.loads(e.read())
                finally:
                    e.close()
    """, rel="pkg/serve/probe.py")
    assert [f for f in out if f.code == "GC12"] == []


def test_gc12_urlopen_chain_flagged(tmp_path):
    out = check_src(tmp_path, """
        import urllib.request

        def fetch(url):
            return urllib.request.urlopen(url).read()
    """, rel="pkg/serve/fetch.py")
    hits = [f for f in out if f.code == "GC12"]
    assert hits and "call chain" in hits[0].message


def test_gc12_outside_scoped_dirs_clean(tmp_path):
    out = check_src(tmp_path, """
        import socket

        def ping(addr):
            s = socket.create_connection(addr)
            return s.recv(4)
    """, rel="pkg/models/conn.py")
    assert [f for f in out if f.code == "GC12"] == []


GC12_OPENER = """
    import socket

    def dial(addr):
        return socket.create_connection(addr)
"""

GC12_CROSS_USER = """
    from pkg.io.opener import dial

    def ping(addr):
        c = dial(addr)
        c.sendall(b'x')
        return c.recv(4)
"""


def test_gc12_cross_module_returned_resource_flagged(tmp_path):
    """A helper RETURNING a fresh socket transfers ownership — the
    returns_resource closure makes the call site an acquisition."""
    out = check_srcs(tmp_path, {"pkg/io/opener.py": GC12_OPENER,
                                "pkg/serve/user.py": GC12_CROSS_USER})
    hits = [f for f in out if f.code == "GC12"]
    assert hits and hits[0].path == "pkg/serve/user.py"


def test_gc12_cross_module_missed_by_single_module_scan(tmp_path):
    out = check_srcs(tmp_path, {"pkg/serve/user.py": GC12_CROSS_USER})
    assert [f for f in out if f.code == "GC12"] == []


def test_gc12_escape_to_thread_owner_clean(tmp_path):
    """The accept-loop shape: a fresh connection handed straight to a
    handler thread is the handler's to close."""
    out = check_src(tmp_path, """
        import socket
        import threading

        class L:
            def accept_loop(self):
                while True:
                    conn, _ = self._sock.accept()
                    threading.Thread(target=self._serve,
                                     args=(conn,), daemon=True).start()
    """, rel="pkg/serve/listener.py")
    assert [f for f in out if f.code == "GC12"] == []


# -- engine v3: parallel scan, wall breakdown, --fix gc09 ------------------

def test_parallel_scan_matches_serial(tmp_path):
    """The fork-based 2-worker scan must produce byte-identical findings
    to the serial path (same fingerprints, same order)."""
    files = {}
    for i in range(30):                  # above _PARALLEL_MIN_FILES
        files[f"pkg/serve/m{i:02d}.py"] = """
            import socket
            def ping%d(addr):
                s = socket.create_connection(addr)
                s.sendall(b'x')
                return s.recv(4)
        """ % i
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    serial = run_paths([str(tmp_path)], root=str(tmp_path), jobs=1)
    t_par = {}
    parallel = run_paths([str(tmp_path)], root=str(tmp_path), jobs=2,
                         timings=t_par)
    assert [f.fingerprint for f in serial] == \
        [f.fingerprint for f in parallel]
    assert len(serial) == 30
    assert t_par.get("jobs") == 2
    assert "GC12" in t_par["rules_s"]


def test_rule_wall_breakdown_in_json_out(tmp_path):
    """--json-out carries the per-rule wall breakdown (the <=30 s CI
    budget evidence)."""
    from hivemall_tpu.tools.graftcheck.engine import main as gc_main
    p = tmp_path / "pkg" / "io" / "m.py"
    p.parent.mkdir(parents=True)
    p.write_text("import time\n\ndef wait(d):\n"
                 "    t0 = time.time()\n"
                 "    return time.time() - t0\n")
    report_path = tmp_path / "report.json"
    rc = gc_main([str(tmp_path / "pkg"), "--root", str(tmp_path),
                  "--json-out", str(report_path)])
    assert rc == 1                       # the GC02 finding
    report = json.loads(report_path.read_text())
    wall = report["wall"]
    assert set(wall["rules_s"]) == set(
        __import__("hivemall_tpu.tools.graftcheck.rules",
                   fromlist=["RULES"]).RULES)
    assert wall["total_s"] > 0


def test_fix_gc09_rewrites_np_to_jnp(tmp_path):
    """--fix's mechanical GC09 subset: np.<fn> -> jnp.<fn> on the
    flagged tracer-reaching call lines, same workflow as GC02/GC06."""
    from hivemall_tpu.tools.graftcheck.engine import _apply_fixes
    p = tmp_path / "pkg" / "models" / "m.py"
    p.parent.mkdir(parents=True)
    p.write_text(textwrap.dedent("""
        import jax
        import numpy as np
        import jax.numpy as jnp

        @jax.jit
        def step(w, g):
            return w - np.mean(g)
    """))
    findings = run_paths([str(tmp_path)], root=str(tmp_path))
    fixable = [f for f in findings if f.fix_kind == "gc09-jnp"]
    assert fixable
    diff, fixed = _apply_fixes(findings, str(tmp_path), write=True)
    assert fixed >= 1
    assert "-    return w - np.mean(g)" in diff
    assert "+    return w - jnp.mean(g)" in diff
    # the rewritten tree rescans clean on GC09
    again = run_paths([str(tmp_path)], root=str(tmp_path))
    assert [f for f in again if f.code == "GC09"] == []


def test_fix_gc09_inserts_missing_jnp_import(tmp_path):
    """A flagged module that only imports numpy — exactly the
    host-helper shape GC09 exists to catch — must gain the jnp binding
    with the rewrite, or --fix --write would leave it raising
    NameError at import while the rescan reads clean."""
    from hivemall_tpu.tools.graftcheck.engine import _apply_fixes
    p = tmp_path / "pkg" / "models" / "m.py"
    p.parent.mkdir(parents=True)
    p.write_text(textwrap.dedent("""
        import jax
        import numpy as np

        @jax.jit
        def step(w, g):
            return w - np.mean(g)
    """))
    findings = run_paths([str(tmp_path)], root=str(tmp_path))
    assert [f for f in findings if f.fix_kind == "gc09-jnp"]
    diff, fixed = _apply_fixes(findings, str(tmp_path), write=True)
    assert fixed >= 1
    assert "+import jax.numpy as jnp" in diff
    text = p.read_text()
    # the binding lands right after the numpy import, before first use
    assert text.index("import jax.numpy as jnp") > text.index(
        "import numpy as np")
    assert text.index("import jax.numpy as jnp") < text.index("jnp.mean")
    compile(text, str(p), "exec")        # still a valid module
    again = run_paths([str(tmp_path)], root=str(tmp_path))
    assert [f for f in again if f.code == "GC09"] == []


def test_fix_gc09_scopes_rewrite_to_twin_calls(tmp_path):
    """The mechanical rewrite must not mint jnp.random/jnp.save
    AttributeErrors or mutate string/comment text on a flagged line —
    only twin-allowlisted np.<fn> calls in code spans change, and a
    non-twin finding survives the rescan for a human."""
    from hivemall_tpu.tools.graftcheck.engine import _apply_fixes
    p = tmp_path / "pkg" / "models" / "m.py"
    p.parent.mkdir(parents=True)
    p.write_text(textwrap.dedent("""
        import jax
        import numpy as np
        import jax.numpy as jnp

        @jax.jit
        def step(w, g):
            w = w - np.mean(g) + len("np.sum x")  # np.sum comment
            np.save("x.npy", w)
            return w
    """))
    findings = run_paths([str(tmp_path)], root=str(tmp_path))
    assert [f for f in findings if f.fix_kind == "gc09-jnp"]
    _apply_fixes(findings, str(tmp_path), write=True)
    text = p.read_text()
    assert "jnp.mean(g)" in text                  # the twin rewrote
    assert 'len("np.sum x")' in text              # string untouched
    assert "# np.sum comment" in text             # comment untouched
    assert 'np.save("x.npy", w)' in text          # no jnp.save minted
    again = run_paths([str(tmp_path)], root=str(tmp_path))
    assert [f for f in again if f.code == "GC09"]  # np.save still flagged


def test_extract_module_degrades_per_function(tmp_path, monkeypatch):
    """One intractable function degrades ALONE — the module's stubs
    (GC05's raw material) and sibling summaries survive instead of the
    whole module vanishing from the project index."""
    from hivemall_tpu.tools.graftcheck import engine as eng
    from hivemall_tpu.tools.graftcheck import interproc
    from hivemall_tpu.tools.graftcheck.rules import collect_project
    p = tmp_path / "pkg" / "obs" / "reg.py"
    p.parent.mkdir(parents=True)
    p.write_text(textwrap.dedent("""
        FOO_STUB = {"a": 1, "b": 2}

        def good():
            return 1

        def poison():
            return 2
    """))
    ctx, err = eng._parse_one(str(p), "pkg/obs/reg.py")
    assert err is None and ctx is not None
    real = interproc._summarize_function

    def boom(ctx_, mi, fn, cls, direct, bare):
        if fn.name == "poison":
            raise RuntimeError("seeded analyzer crash")
        return real(ctx_, mi, fn, cls, direct, bare)

    monkeypatch.setattr(interproc, "_summarize_function", boom)
    project = collect_project([ctx])
    assert "FOO_STUB" in project.stubs            # stubs survived
    assert project.interproc is not None
    names = {fid[1] for fid in project.interproc.functions
             if fid[0] == "pkg/obs/reg.py"}
    assert "good" in names                        # sibling summarized
    assert "poison" not in names                  # only the bad one gone


def test_selfcheck_covers_v3_rules():
    """Every GC09-GC12 fixture is wired into --selfcheck (the CI proof
    that the new rules fire)."""
    from hivemall_tpu.tools.graftcheck.engine import _FIXTURES
    want = {"GC09", "GC10", "GC11", "GC12"}
    seeded = set()
    for _rel, (_src, codes_) in _FIXTURES.items():
        seeded |= codes_
    assert want <= seeded
