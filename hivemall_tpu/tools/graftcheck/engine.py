"""graftcheck engine — file walking, suppressions, baseline, cache, CLI.

Two passes over the scanned tree: pass 1 parses every file and collects
the cross-file :class:`~.rules.ProjectIndex` (registry stub constants +
alias functions, plus the interprocedural summary index), pass 2 runs
every rule per module. Suppression comments (``# graftcheck:
disable=GC02`` — trailing on the flagged line, or alone on the line
above) are honored before the baseline is applied.

Baseline semantics (``--baseline graftcheck_baseline.json``): a JSON
list of finding fingerprints tolerated for now. The gate fails on any
NON-baselined finding AND on any stale entry — a fixed finding must
leave the baseline in the same PR, so the debt list only ever shrinks.

Findings cache (``.graftcheck_cache.json`` under the scan root):
content-hashed and stamped with :data:`~.rules.RULESTAMP`. Because the
rules are INTERPROCEDURAL, per-file reuse is unsound — editing one file
can change another file's findings through the summary index — so
invalidation is whole-scan: when the rule stamp, the scanned file set
and every file's sha256 match the cache, the findings are replayed with
zero parsing (the CI re-run case); any difference re-analyzes
everything (a few seconds). ``--no-cache`` bypasses both directions.
"""

from __future__ import annotations

import argparse
import ast
import difflib
import hashlib
import io
import json
import os
import re
import sys
import tokenize
from typing import Dict, Iterable, List, Optional, Set, Tuple

import time

from . import interproc
from .rules import (Finding, ModuleContext, ProjectIndex, RULES,
                    RULESTAMP, collect_project, project_from_facts,
                    run_rules)

__all__ = ["Finding", "run_paths", "scan_file", "load_baseline",
           "write_baseline", "main"]

_DIRECTIVE = re.compile(r"graftcheck:\s*disable=([A-Z0-9,\s]+)")
_SKIP_DIRS = {"__pycache__", ".git", ".venv", "node_modules"}

CACHE_NAME = ".graftcheck_cache.json"


def iter_py_files(paths: Iterable[str]) -> Iterable[str]:
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            yield p
        elif os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = sorted(d for d in dirnames
                                     if d not in _SKIP_DIRS
                                     and not d.startswith("."))
                for f in sorted(filenames):
                    if f.endswith(".py"):
                        yield os.path.join(dirpath, f)


def _comment_map(source: str) -> Tuple[Dict[int, str], Set[int]]:
    """line -> comment text, plus the set of comment-ONLY lines (a
    directive alone on its own line applies to the next code line)."""
    comments: Dict[int, str] = {}
    only: Set[int] = set()
    try:
        toks = list(tokenize.generate_tokens(io.StringIO(source).readline))
    except (tokenize.TokenError, IndentationError):
        return comments, only
    code_lines: Set[int] = set()
    for tok in toks:
        if tok.type == tokenize.COMMENT:
            comments[tok.start[0]] = tok.string
        elif tok.type not in (tokenize.NL, tokenize.NEWLINE,
                              tokenize.INDENT, tokenize.DEDENT,
                              tokenize.ENCODING, tokenize.ENDMARKER):
            for line in range(tok.start[0], tok.end[0] + 1):
                code_lines.add(line)
    only = {ln for ln in comments if ln not in code_lines}
    return comments, only


def _suppressions(comments: Dict[int, str],
                  comment_only: Set[int]) -> Dict[int, Set[str]]:
    """Effective per-line suppressed codes: a trailing directive covers
    its own line; a directive alone on a line covers the next line."""
    supp: Dict[int, Set[str]] = {}
    for line, text in comments.items():
        m = _DIRECTIVE.search(text)
        if not m:
            continue
        codes = {c.strip() for c in m.group(1).split(",") if c.strip()}
        supp.setdefault(line, set()).update(codes)
        if line in comment_only:
            supp.setdefault(line + 1, set()).update(codes)
    return supp


def _parse_one(path: str, relpath: str) \
        -> Tuple[Optional[ModuleContext], Optional[Finding]]:
    try:
        with open(path, "r", encoding="utf-8", errors="replace") as f:
            source = f.read()
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return None, Finding("GC00", relpath, e.lineno or 0, 0,
                             f"syntax error: {e.msg}",
                             "graftcheck cannot analyze unparseable "
                             "source", "<module>")
    comments, only = _comment_map(source)
    ctx = ModuleContext(relpath, tree, comments)
    ctx.suppressions = _suppressions(comments, only)  # type: ignore
    return ctx, None


def scan_file(path: str, root: Optional[str] = None,
              project: Optional[ProjectIndex] = None) -> List[Finding]:
    """Analyze one file (convenience for tests); cross-file GC05 parity
    and interprocedural edges only see this file unless ``project`` is
    given."""
    rel = os.path.relpath(path, root or os.getcwd()).replace(os.sep, "/")
    ctx, err = _parse_one(path, rel)
    if err is not None:
        return [err]
    assert ctx is not None
    if project is None:
        project = collect_project([ctx])
    return _apply_suppressions(ctx, run_rules(ctx, project))


def _apply_suppressions(ctx: ModuleContext,
                        findings: List[Finding]) -> List[Finding]:
    supp = getattr(ctx, "suppressions", {})
    return [f for f in findings if f.code not in supp.get(f.line, set())]


# -- findings cache ---------------------------------------------------------

def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 16), b""):
            h.update(chunk)
    return h.hexdigest()


def _finding_from_json(d: dict) -> Finding:
    return Finding(code=d["code"], path=d["path"], line=d["line"],
                   col=d["col"], message=d["message"],
                   hint=d.get("hint", ""),
                   symbol=d.get("symbol", "<module>"),
                   fix_kind=d.get("fix_kind"),
                   fix_lines=tuple(d.get("fix_lines", ())))


def _cache_load(cache_path: str, shas: Dict[str, str]) \
        -> Optional[List[Finding]]:
    """Replay cached findings iff the rule stamp, the file SET and every
    file's content hash match — else None (full re-analysis)."""
    try:
        with open(cache_path, "r", encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError, json.JSONDecodeError):
        return None
    if data.get("stamp") != RULESTAMP:
        return None
    cached = data.get("files")
    if not isinstance(cached, dict) or set(cached) != set(shas):
        return None
    for rel, entry in cached.items():
        if not isinstance(entry, dict) or entry.get("sha") != shas[rel]:
            return None                  # mangled entry: just re-scan
    try:
        out = [_finding_from_json(d)
               for entry in cached.values()
               for d in entry.get("findings", [])]
    except (KeyError, TypeError):
        return None
    out.sort(key=lambda f: (f.path, f.line, f.code))
    return out


def _cache_store(cache_path: str, shas: Dict[str, str],
                 findings: List[Finding]) -> None:
    by_file: Dict[str, List[dict]] = {rel: [] for rel in shas}
    for f in findings:
        by_file.setdefault(f.path, []).append(f.to_json())
    data = {"stamp": RULESTAMP,
            "comment": "graftcheck findings cache — whole-scan "
                       "invalidation (interprocedural rules make "
                       "per-file reuse unsound); delete freely",
            "files": {rel: {"sha": sha,
                            "findings": by_file.get(rel, [])}
                      for rel, sha in shas.items()}}
    tmp = cache_path + ".tmp"
    try:
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(data, f)
        os.replace(tmp, cache_path)
    except OSError:
        pass                             # a read-only tree just re-scans


def _worker_main(conn, shard: List[Tuple[str, str]]) -> None:
    """One scan worker: parse + extract facts for its shard, ship the
    (picklable) facts to the main process, receive the assembled
    project view back, run the rule pass on the contexts it kept.
    Fork-spawned — the shard arrives through the closure-free args so
    the protocol also survives a spawn start method."""
    try:
        contexts: List[ModuleContext] = []
        errors: List[Finding] = []
        facts = []
        for rel, ap in shard:
            ctx, err = _parse_one(ap, rel)
            if err is not None:
                errors.append(err)
                continue
            assert ctx is not None
            try:
                facts.append(interproc.extract_module(ctx))
            except Exception:  # noqa: BLE001 — degrade to unknown
                pass
            contexts.append(ctx)
        conn.send(("facts", facts, errors))
        msg = conn.recv()
        if not (isinstance(msg, tuple) and msg and msg[0] == "project"):
            return
        project: ProjectIndex = msg[1]
        rule_wall: Dict[str, float] = {}
        findings: List[Finding] = []
        for ctx in contexts:
            findings.extend(_apply_suppressions(
                ctx, run_rules(ctx, project, rule_wall)))
        conn.send(("findings", findings, rule_wall))
    except Exception:  # noqa: BLE001 — the main process falls back
        import traceback
        try:
            conn.send(("error", traceback.format_exc()))
        except Exception:  # noqa: BLE001 — pipe already gone
            pass
    finally:
        conn.close()


def _run_parallel(files: Dict[str, str], jobs: int,
                  timings: Optional[dict]) -> Optional[List[Finding]]:
    """Fan the parse/summary pass AND the rule pass across ``jobs``
    worker processes (the 2-CPU CI container is the floor this exists
    for). Returns None on ANY failure — the caller falls back to the
    serial path, so a multiprocessing quirk can never take the gate
    down."""
    try:
        import multiprocessing as mp
        mpc = mp.get_context("fork")
    except (ImportError, ValueError):
        return None
    # balance shards by size: big modules dominate the summary pass
    def _size(kv):
        try:
            return -os.path.getsize(kv[1])
        except OSError:
            return 0                     # vanished mid-scan: the worker
            #                              degrades it to a parse error
    sized = sorted(files.items(), key=_size)
    shards = [sized[i::jobs] for i in range(jobs)]
    shards = [s for s in shards if s]
    procs, conns = [], []
    t0 = time.perf_counter()
    try:
        for shard in shards:
            parent, child = mpc.Pipe()
            p = mpc.Process(target=_worker_main, args=(child, shard),
                            daemon=True)
            p.start()
            child.close()
            procs.append(p)
            conns.append(parent)
        all_facts, findings = [], []
        for parent in conns:
            msg = parent.recv()
            if msg[0] != "facts":
                raise RuntimeError(f"worker failed: {msg[1][:2000]}")
            all_facts.extend(msg[1])
            findings.extend(msg[2])
        t1 = time.perf_counter()
        project = project_from_facts(all_facts)
        t2 = time.perf_counter()
        for parent in conns:
            parent.send(("project", project))
        rule_wall: Dict[str, float] = {}
        for parent in conns:
            msg = parent.recv()
            if msg[0] != "findings":
                raise RuntimeError(f"worker failed: {msg[1][:2000]}")
            findings.extend(msg[1])
            for k, v in msg[2].items():
                # workers run each rule concurrently over disjoint
                # shards — the busiest worker IS the rule's wall-clock
                # contribution; summing would report CPU-seconds that
                # grow with --jobs and overstate the CI budget
                rule_wall[k] = max(rule_wall.get(k, 0.0), v)
        if timings is not None:
            timings["jobs"] = len(shards)
            timings["rules_s"] = {k: round(v, 4)
                                  for k, v in sorted(rule_wall.items())}
            timings["phases_s"] = {
                "parse_extract": round(t1 - t0, 4),
                "assemble": round(t2 - t1, 4),
                "rules": round(time.perf_counter() - t2, 4),
            }
        return findings
    except Exception:  # noqa: BLE001 — serial fallback handles it
        return None
    finally:
        for parent in conns:
            try:
                parent.close()
            except OSError:
                pass
        for p in procs:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()


def _run_serial(files: Dict[str, str],
                timings: Optional[dict]) -> List[Finding]:
    t0 = time.perf_counter()
    contexts: List[ModuleContext] = []
    findings: List[Finding] = []
    for rel, ap in files.items():
        ctx, err = _parse_one(ap, rel)
        if err is not None:
            findings.append(err)
            continue
        assert ctx is not None
        contexts.append(ctx)
    project = collect_project(contexts)
    t1 = time.perf_counter()
    rule_wall: Dict[str, float] = {}
    for ctx in contexts:
        findings.extend(_apply_suppressions(
            ctx, run_rules(ctx, project, rule_wall)))
    if timings is not None:
        timings["jobs"] = 1
        timings["rules_s"] = {k: round(v, 4)
                              for k, v in sorted(rule_wall.items())}
        timings["phases_s"] = {
            "parse_extract_assemble": round(t1 - t0, 4),
            "rules": round(time.perf_counter() - t1, 4),
        }
    return findings


#: below this many files the fork+pickle overhead outweighs the win
#: (selfcheck scratch trees and single-file scans stay serial)
_PARALLEL_MIN_FILES = 24


def run_paths(paths: Iterable[str], root: Optional[str] = None,
              cache: Optional[str] = None, jobs: Optional[int] = None,
              timings: Optional[dict] = None) -> List[Finding]:
    """Scan every .py under ``paths``; returns suppression-filtered
    findings (baseline is the caller's concern). Paths in findings are
    relative to ``root`` (default: cwd), '/'-separated — baseline
    fingerprints stay stable across machines. ``cache``: path of the
    findings cache to consult/update (None = no caching). ``jobs``:
    worker processes for the parse/summary + rule passes (default: the
    CPU count; 1 forces serial). ``timings``: optional dict that
    receives the per-rule and per-phase wall breakdown."""
    root = os.path.abspath(root or os.getcwd())
    files: Dict[str, str] = {}           # rel -> abs
    for path in iter_py_files(paths):
        ap = os.path.abspath(path)
        rel = os.path.relpath(ap, root).replace(os.sep, "/")
        files[rel] = ap

    shas: Optional[Dict[str, str]] = None
    if cache:
        shas = {rel: _sha256_file(ap) for rel, ap in files.items()}
        cached = _cache_load(cache, shas)
        if cached is not None:
            if timings is not None:
                timings["cached"] = True
            return cached

    njobs = jobs if jobs is not None else (os.cpu_count() or 1)
    findings: Optional[List[Finding]] = None
    if njobs >= 2 and len(files) >= _PARALLEL_MIN_FILES:
        findings = _run_parallel(files, njobs, timings)
    if findings is None:
        findings = _run_serial(files, timings)
    findings.sort(key=lambda f: (f.path, f.line, f.code))
    if cache and shas is not None:
        _cache_store(cache, shas, findings)
    return findings


# -- baseline ---------------------------------------------------------------

def load_baseline(path: str) -> List[str]:
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    if isinstance(data, dict):
        data = data.get("findings", [])
    if not isinstance(data, list) \
            or not all(isinstance(x, str) for x in data):
        raise ValueError(f"{path}: baseline must be a JSON list of "
                         f"fingerprint strings (or {{'findings': [...]}})")
    return data


def write_baseline(path: str, findings: List[Finding]) -> None:
    data = {"version": 1,
            "comment": "graftcheck debt list — fixing a finding MUST "
                       "remove its entry (the gate flags stale entries); "
                       "see docs/STATIC_ANALYSIS.md",
            "findings": sorted(f.fingerprint for f in findings)}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1)
        f.write("\n")


def gate(findings: List[Finding], baseline: List[str],
         covered: Optional[List[str]] = None) \
        -> Tuple[List[Finding], List[str]]:
    """(new findings not in baseline, stale baseline entries).

    ``covered`` — scan-root prefixes (relpaths, '/'-separated): an entry
    is judged stale only when its file lies UNDER a scanned root; a
    partial scan (one file/dir) must not flag the rest of the repo's
    baseline as stale. ``None`` = the scan covered everything."""
    prints = {f.fingerprint for f in findings}
    base = set(baseline)
    fresh = [f for f in findings if f.fingerprint not in base]

    def in_scope(fp: str) -> bool:
        if covered is None:
            return True
        path = fp.split("::", 1)[0]
        return any(p in (".", "") or path == p or path.startswith(p + "/")
                   for p in covered)

    stale = sorted(fp for fp in base - prints if in_scope(fp))
    return fresh, stale


# -- mechanical fixes (--fix) -----------------------------------------------

_GC06_ANNOTATION = ("  # isolation: TODO(graftcheck --fix) name why "
                    "this catch-all is required")

# np.<fn> names with a drop-in jnp twin — the ONLY rewrites the
# mechanical GC09 fix may make; anything else on a flagged line
# (np.random.*, I/O, twin-less APIs) stays for a human and the finding
# survives the rescan
_JNP_TWINS = frozenset((
    "abs", "absolute", "add", "all", "any", "arange", "argmax",
    "argmin", "argsort", "array", "asarray", "ceil", "clip",
    "concatenate", "cos", "cumprod", "cumsum", "diag", "divide", "dot",
    "einsum", "exp", "expand_dims", "eye", "floor", "full", "full_like",
    "inner", "isfinite", "isinf", "isnan", "linspace", "log", "log10",
    "log1p", "log2", "matmul", "max", "maximum", "mean", "median",
    "min", "minimum", "multiply", "ones", "ones_like", "outer",
    "power", "prod", "reshape", "round", "sign", "sin", "sort", "split",
    "sqrt", "square", "squeeze", "stack", "std", "subtract", "sum",
    "take", "tanh", "tensordot", "transpose", "tril", "triu", "unique",
    "var", "where", "zeros", "zeros_like",
))


def _in_noncode(line: str, pos: int) -> bool:
    """True when ``pos`` sits inside a string literal or after a ``#``
    comment marker — spans a mechanical rewrite must never touch."""
    q = None
    i = 0
    while i < pos:
        c = line[i]
        if q is not None:
            if c == "\\":
                i += 2
                continue
            if c == q:
                q = None
        elif c in "\"'":
            q = c
        elif c == "#":
            return True
        i += 1
    return q is not None


def _sub_np_jnp(line: str) -> str:
    """``np.<fn>`` → ``jnp.<fn>`` on ONE flagged line — only for fns
    with a drop-in jnp twin, never inside strings or comments (a
    blanket rewrite would mint ``jnp.random...`` AttributeErrors and
    mutate log text)."""
    def repl(m: "re.Match[str]") -> str:
        if m.group(1) not in _JNP_TWINS or _in_noncode(line, m.start()):
            return m.group(0)
        return "jnp." + m.group(1)
    return re.sub(r"\b(?:np|numpy)\.([A-Za-z_][A-Za-z0-9_]*)",
                  repl, line)


def _apply_fixes(findings: List[Finding], root: str,
                 write: bool) -> Tuple[str, int]:
    """Build the mechanical rewrites for fixable findings. Returns
    (unified diff across all touched files, number of findings fixed);
    with ``write`` the new contents also land on disk.

    GC02 ``gc02-monotonic``: every literal ``time.time()`` on the
    finding's fix lines becomes ``time.monotonic()`` (the flagged
    arithmetic plus the taint-source assignments). GC06
    ``gc06-annotate``: the bare handler line gains a TODO annotation
    comment — the rule passes, and the placeholder text keeps a human
    on the hook for the real why.
    """
    per_file: Dict[str, Dict[int, str]] = {}   # rel -> line -> kind
    for f in findings:
        if f.fix_kind is None:
            continue
        for ln in (f.fix_lines or (f.line,)):
            per_file.setdefault(f.path, {})[ln] = f.fix_kind
    chunks: List[str] = []
    changed: Dict[str, Set[int]] = {}          # rel -> lines rewritten
    for rel in sorted(per_file):
        ap = os.path.join(root, rel.replace("/", os.sep))
        try:
            with open(ap, "r", encoding="utf-8") as fh:
                old_lines = fh.readlines()
        except OSError:
            continue
        new_lines = list(old_lines)
        for ln, kind in per_file[rel].items():
            i = ln - 1
            if not (0 <= i < len(new_lines)):
                continue
            if kind == "gc02-monotonic":
                new_lines[i] = new_lines[i].replace(
                    "time.time()", "time.monotonic()")
            elif kind == "gc09-jnp":
                # the mechanical GC09 subset: a numpy call on a traced
                # value becomes its jnp twin (twin-allowlisted, code
                # spans only — see _sub_np_jnp)
                new_lines[i] = _sub_np_jnp(new_lines[i])
            elif kind == "gc06-annotate":
                stripped = new_lines[i].rstrip("\n")
                if "#" not in stripped:
                    new_lines[i] = stripped + _GC06_ANNOTATION + "\n"
            if new_lines[i] != old_lines[i]:
                changed.setdefault(rel, set()).add(ln)
        if (any(per_file[rel].get(ln) == "gc09-jnp"
                for ln in changed.get(rel, ()))
                and not re.search(
                    r"^\s*(?:import\s+jax\.numpy\s+as\s+jnp\b"
                    r"|from\s+jax\s+import\s+numpy\s+as\s+jnp\b)",
                    "".join(new_lines), re.M)):
            # the rewrite references jnp — a module that only imported
            # numpy must gain the binding or --fix --write would leave
            # it raising NameError at import
            at = 0
            for i, txt in enumerate(new_lines):
                if re.match(r"(?:import|from)\s+numpy\b", txt):
                    at = i + 1
                    break
                if at == 0 and re.match(r"(?:import|from)\s+\w", txt):
                    at = i + 1           # fallback: after first import
            new_lines.insert(at, "import jax.numpy as jnp\n")
        if new_lines == old_lines:
            continue
        chunks.append("".join(difflib.unified_diff(
            old_lines, new_lines, fromfile=f"a/{rel}",
            tofile=f"b/{rel}")))
        if write:
            with open(ap, "w", encoding="utf-8") as fh:
                fh.writelines(new_lines)
    # a finding counts as fixed only when a line it owns actually
    # changed — a fixable-flagged finding whose rewrite was a no-op must
    # not let `--fix --write` report success on an unchanged file
    fixed = sum(
        1 for f in findings if f.fix_kind is not None
        and changed.get(f.path, set())
        & set(f.fix_lines or (f.line,)))
    return "".join(chunks), fixed


# -- selfcheck --------------------------------------------------------------

_FIXTURES = {
    # one seeded violation per rule — the gate must catch every one.
    # pkg/... fixture modules import each other with absolute names
    # (pkg.x.y) so the interprocedural resolver links them exactly as it
    # links real modules.
    "pkg/models/bad_model.py": (
        "import jax\n"
        "from functools import lru_cache\n\n"
        "def per_call_predict(f, x):\n"
        "    g = jax.jit(f)\n"
        "    return g(x)\n\n"
        "def nested_factory():\n"
        "    @lru_cache(maxsize=8)\n"
        "    def build(n):\n"
        "        return jax.jit(lambda v: v * n)\n"
        "    return build\n",
        {"GC01"}),
    "pkg/io/bad_io.py": (
        "import time\n\n"
        "def save_pointer(path, blob):\n"
        "    with open(path, 'w') as f:\n"
        "        f.write(blob)\n\n"
        "def wait(deadline_s):\n"
        "    deadline = time.time() + deadline_s\n"
        "    while time.time() < deadline:\n"
        "        pass\n",
        {"GC02", "GC03"}),
    "pkg/serve/bad_serve.py": (
        "import threading\n\n"
        "class W:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.count = 0\n"
        "        threading.Thread(target=self._a).start()\n"
        "        threading.Thread(target=self._b).start()\n"
        "    def _a(self):\n"
        "        self.count += 1\n"
        "    def _b(self):\n"
        "        try:\n"
        "            self.count -= 1\n"
        "        except Exception:\n"
        "            pass\n",
        {"GC04", "GC06"}),
    "pkg/obs/registry.py": (
        "FOO_STUB = {'ok': 0, 'bad-dash': 0}\n\n"
        "class P:\n"
        "    def obs_section(self):\n"
        "        return {'ok': 0, 'extra': 1}\n"
        "    def _register_obs(self):\n"
        "        def p():\n"
        "            return (self.obs_section() if self is not None\n"
        "                    else dict(FOO_STUB))\n"
        "        registry.register('bad.name', p)\n",
        {"GC05"}),
    # GC05 on the ISSUE-13 `retrain` section specifically: a provider
    # whose keys drift from RETRAIN_STUB must be caught the same way
    # (the autopilot's state machine is dashboard-keyed)
    "pkg/obs/retrain_registry.py": (
        "RETRAIN_STUB = {'state': 'idle', 'attempts': 0}\n\n"
        "class R:\n"
        "    def obs_section(self):\n"
        "        return {'state': 'idle', 'extra_key': 1}\n"
        "    def _register_obs(self):\n"
        "        def p():\n"
        "            return (self.obs_section() if self is not None\n"
        "                    else dict(RETRAIN_STUB))\n"
        "        registry.register('retrain', p)\n",
        {"GC05"}),
    # GC07: a direct fetch in a per-step loop, and a call to a helper
    # that fetches (one function boundary away)
    "pkg/models/bad_hot.py": (
        "import numpy as np\n\n"
        "def fetch_loss(x):\n"
        "    return float(np.asarray(x))\n\n"
        "def train(step, batches):\n"
        "    losses = []\n"
        "    for b in batches:\n"
        "        out = step(b)\n"
        "        losses.append(fetch_loss(out))\n"
        "    return losses\n",
        {"GC07"}),
    # GC08: a stored looping thread no shutdown path ever joins/signals
    "pkg/serve/bad_thread.py": (
        "import threading\n\n"
        "class Daemon:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._run,\n"
        "                                   daemon=True)\n"
        "        self._t.start()\n"
        "    def _run(self):\n"
        "        while True:\n"
        "            pass\n",
        {"GC08"}),
    # interprocedural upgrades: each pair is INVISIBLE to the PR 11
    # intra-module analysis (tests/test_graftcheck.py pins the
    # single-module miss); the summaries must connect them
    "pkg/utils/clockutil.py": (
        "import time\n\n"
        "def now_s():\n"
        "    return time.time()\n",
        set()),
    "pkg/io/bad_deadline.py": (
        "from pkg.utils.clockutil import now_s\n\n"
        "def wait(seconds):\n"
        "    deadline = now_s() + seconds\n"
        "    while now_s() < deadline:\n"
        "        pass\n",
        {"GC02"}),
    "pkg/ops/jit_factory.py": (
        "import jax\n\n"
        "def make_step(f):\n"
        "    return jax.jit(f)\n",
        set()),
    "pkg/models/bad_factory_use.py": (
        "from pkg.ops.jit_factory import make_step\n\n"
        "def score_all(fns, x):\n"
        "    return [make_step(f)(x) for f in fns]\n",
        {"GC01"}),
    "pkg/serve/attr_helper.py": (
        "def bump_counter(obj):\n"
        "    obj.count += 1\n",
        set()),
    "pkg/serve/bad_cross_write.py": (
        "import threading\n"
        "from pkg.serve.attr_helper import bump_counter\n\n"
        "class X:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.count = 0\n"
        "        threading.Thread(target=self._a).start()\n"
        "        threading.Thread(target=self._b).start()\n"
        "    def _a(self):\n"
        "        bump_counter(self)\n"
        "    def _b(self):\n"
        "        with self._lock:\n"
        "            self.count -= 1\n",
        {"GC04"}),
    # -- v3: the XLA compile contract + resource lifecycle ---------------
    # GC09: np call, cast and Python branch all concretize jit-traced
    # params in one module
    "pkg/models/bad_tracer.py": (
        "import jax\n"
        "import numpy as np\n\n"
        "@jax.jit\n"
        "def step(w, g):\n"
        "    lr = float(np.mean(g))\n"
        "    if g > 0:\n"
        "        w = w - lr * g\n"
        "    return w\n",
        {"GC09"}),
    # GC09 cross-module: the np call lives in a helper that is only
    # traced because a jit body in ANOTHER module hands it a tracer
    "pkg/ops/helper_np.py": (
        "import numpy as np\n\n"
        "def host_norm(v):\n"
        "    return np.sum(v * v)\n",
        {"GC09"}),
    "pkg/models/bad_jit_cross.py": (
        "import jax\n"
        "from pkg.ops.helper_np import host_norm\n\n"
        "@jax.jit\n"
        "def fused(x):\n"
        "    return host_norm(x * 2.0)\n",
        set()),
    # GC10: a Python scalar literal entering the scan carry
    "pkg/ops/bad_scan.py": (
        "import jax\n\n"
        "def run(xs, w):\n"
        "    def body(carry, x):\n"
        "        w, t = carry\n"
        "        return (w + x, 0.0), w\n"
        "    return jax.lax.scan(body, (w, 0.0), xs)\n",
        {"GC10"}),
    # GC10 cross-module: the body with a dtype-changing carry leaf is
    # imported; only the OTHER module's lax.scan marks it a scan body
    "pkg/ops/scan_body.py": (
        "def body(carry, x):\n"
        "    s, t = carry\n"
        "    return (s + x, t.astype('float32')), s\n",
        {"GC10"}),
    "pkg/models/bad_scan_cross.py": (
        "import jax\n"
        "from pkg.ops.scan_body import body\n\n"
        "def run(xs, s0):\n"
        "    return jax.lax.scan(body, s0, xs)\n",
        set()),
    # GC11: an ops/ scannable step core registered without donation
    "pkg/ops/bad_nodonate.py": (
        "import jax\n\n"
        "def scannable(step, core):\n"
        "    step.core = core\n"
        "    return step\n\n"
        "def make_step():\n"
        "    def core(w, s, t, idx):\n"
        "        return w, s, 0.0\n"
        "    return scannable(jax.jit(core), core)\n",
        {"GC11"}),
    # GC11 cross-module: the factory's donation is declared in another
    # module; the caller reads the donated buffer after the call
    "pkg/ops/donate_factory.py": (
        "import jax\n\n"
        "def make_step(core):\n"
        "    return jax.jit(core, donate_argnums=(0, 1))\n",
        set()),
    "pkg/models/bad_donate_read.py": (
        "from pkg.ops.donate_factory import make_step\n\n"
        "def train(core, w, s, xs):\n"
        "    step = make_step(core)\n"
        "    w2, s2 = step(w, s)\n"
        "    return w2, s2, w.sum()\n",
        {"GC11"}),
    # GC12: straight-line-only close + the HTTPError probe shape
    "pkg/serve/bad_leak.py": (
        "import socket\n"
        "import urllib.error\n"
        "import urllib.request\n\n"
        "def probe(addr):\n"
        "    s = socket.create_connection(addr)\n"
        "    s.sendall(b'ping')\n"
        "    data = s.recv(16)\n"
        "    s.close()\n"
        "    return data\n\n"
        "def fetch(url):\n"
        "    try:\n"
        "        with urllib.request.urlopen(url) as r:\n"
        "            return r.read()\n"
        "    except urllib.error.HTTPError as e:\n"
        "        return e.read()\n",
        {"GC12"}),
    # GC12 cross-module: the acquisition hides behind a helper that
    # RETURNS the fresh socket (returns_resource closure)
    "pkg/io/opener.py": (
        "import socket\n\n"
        "def dial(addr):\n"
        "    return socket.create_connection(addr)\n",
        set()),
    "pkg/serve/bad_cross_leak.py": (
        "from pkg.io.opener import dial\n\n"
        "def ping(addr):\n"
        "    c = dial(addr)\n"
        "    c.sendall(b'x')\n"
        "    return c.recv(4)\n",
        {"GC12"}),
}


def selfcheck() -> int:
    """Prove the gate in both directions before trusting a clean run:
    every rule (including the interprocedural upgrades and GC07/GC08)
    fires on its seeded fixture; a baseline silences them; a fixed
    finding turns its baseline entry stale (nonzero); and the tsan
    lockset sanitizer detects the re-seeded PR 11
    ``last_reload_error`` race while passing its lock-guarded twin."""
    import shutil
    import tempfile
    tmp = tempfile.mkdtemp(prefix="graftcheck_selfcheck_")
    try:
        for rel, (src, _want) in _FIXTURES.items():
            p = os.path.join(tmp, rel)
            os.makedirs(os.path.dirname(p), exist_ok=True)
            with open(p, "w", encoding="utf-8") as f:
                f.write(src)
        findings = run_paths([os.path.join(tmp, "pkg")], root=tmp)
        got = {}
        for f in findings:
            got.setdefault(f.path, set()).add(f.code)
        failures = []
        for rel, (_src, want) in _FIXTURES.items():
            missing = want - got.get(rel, set())
            if missing:
                failures.append(f"{rel}: rule(s) {sorted(missing)} did "
                                f"not fire on the seeded violation")
        if not findings:
            failures.append("no findings at all on the seeded tree")
        # direction 2: baseline silences, then goes stale after a "fix"
        bl = os.path.join(tmp, "baseline.json")
        write_baseline(bl, findings)
        fresh, stale = gate(findings, load_baseline(bl))
        if fresh or stale:
            failures.append("baselined tree did not gate clean")
        kept = [f for f in findings if f.code != "GC03"]
        fresh, stale = gate(kept, load_baseline(bl))
        if not stale:
            failures.append("fixed finding did not turn its baseline "
                            "entry stale")
        # direction 3: the DYNAMIC layer — the lockset sanitizer must
        # flag the re-seeded PR 11 PredictEngine.last_reload_error race
        # (two unguarded writer threads) and stay quiet on the guarded
        # twin; a sanitizer that cannot fail is not a gate
        try:
            from ...testing import tsan
            ok, detail = tsan.selfcheck_race()
            if not ok:
                failures.append(f"tsan selfcheck: {detail}")
            tsan_msg = detail
        except Exception as e:  # noqa: BLE001 — a broken sanitizer
            failures.append(f"tsan selfcheck crashed: "
                            f"{type(e).__name__}: {e}")
            tsan_msg = "unavailable"
        # direction 4: the leak sanitizer (GC12's dynamic twin) must
        # catch a seeded fd leak and pass the closed twin
        try:
            from ...testing import leaktrack
            ok, detail = leaktrack.selfcheck_leak()
            if not ok:
                failures.append(f"leaktrack selfcheck: {detail}")
            leak_msg = detail
        except Exception as e:  # noqa: BLE001 — a broken sanitizer
            failures.append(f"leaktrack selfcheck crashed: "
                            f"{type(e).__name__}: {e}")
            leak_msg = "unavailable"
        if failures:
            for msg in failures:
                print(f"graftcheck --selfcheck FAIL: {msg}",
                      file=sys.stderr)
            return 1
        print(f"graftcheck --selfcheck: {len(findings)} seeded findings "
              f"caught across {len(_FIXTURES)} fixtures (incl. "
              f"cross-module GC01/GC02/GC04 + GC07-GC12); baseline gate "
              f"bidirectional (silences fresh, flags stale); "
              f"tsan: {tsan_msg}; leaktrack: {leak_msg}")
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# -- CLI --------------------------------------------------------------------

def _default_paths() -> List[str]:
    """The full repo surface: the installed package tree plus the repo's
    out-of-package Python — tests/ and the graft entry point —
    so deadline idioms and thread workers in the harness obey the same
    invariants the package does (works from any cwd; paths that don't
    exist in an installed-package context are skipped)."""
    pkg = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    repo = os.path.dirname(pkg)
    extras = [os.path.join(repo, "tests"),
              os.path.join(repo, "__graft_entry__.py")]
    return [pkg] + [p for p in extras if os.path.exists(p)]


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m hivemall_tpu.tools.graftcheck",
        description="project-invariant static analyzer "
                    "(docs/STATIC_ANALYSIS.md)")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to scan (default: the hivemall_tpu "
                         "package + tests/ + the graft entry)")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON (default: ./graftcheck_baseline"
                         ".json when present)")
    ap.add_argument("--write-baseline", metavar="PATH",
                    help="write current findings as the new baseline and "
                         "exit 0")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings on stdout")
    ap.add_argument("--json-out", metavar="PATH", default=None,
                    help="also write the full JSON report (all findings "
                         "+ gate verdict) to PATH — the CI artifact")
    ap.add_argument("--selfcheck", action="store_true",
                    help="prove every rule fires on seeded violations, "
                         "the baseline gate works both ways, and the "
                         "tsan sanitizer flags the seeded race")
    ap.add_argument("--root", default=None,
                    help="path-relativity root for fingerprints "
                         "(default: cwd)")
    ap.add_argument("--no-cache", action="store_true",
                    help="bypass the content-hash findings cache")
    ap.add_argument("--jobs", type=int, default=None, metavar="N",
                    help="worker processes for the parse/summary and "
                         "rule passes (default: CPU count; 1 = serial)")
    ap.add_argument("--fix", action="store_true",
                    help="emit a unified diff fixing the mechanical "
                         "rules (GC02 time.time()->time.monotonic(), "
                         "GC09 np.<fn> -> jnp.<fn> on traced values, "
                         "GC06 annotation insertion)")
    ap.add_argument("--write", action="store_true",
                    help="with --fix: rewrite the files in place "
                         "instead of only printing the diff")
    args = ap.parse_args(argv)

    if args.selfcheck:
        return selfcheck()
    if args.write and not args.fix:
        print("graftcheck: --write requires --fix", file=sys.stderr)
        return 2

    paths = args.paths or _default_paths()
    root = args.root
    if root is None and not args.paths:
        # default scan: relative to the repo root (the package's parent)
        pkg = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        root = os.path.dirname(pkg)
    abs_root = os.path.abspath(root or os.getcwd())
    cache = None
    if not args.no_cache and not args.fix and not args.paths:
        # the default full scan only: an explicit-path scan would drop
        # the cache file in the caller's cwd AND evict the whole-tree
        # cache (the cache is keyed by the scanned file SET)
        cache = os.path.join(abs_root, CACHE_NAME)
    timings: dict = {}
    t_scan = time.perf_counter()
    findings = run_paths(paths, root=root, cache=cache, jobs=args.jobs,
                         timings=timings)
    timings["total_s"] = round(time.perf_counter() - t_scan, 4)

    if args.write_baseline:
        write_baseline(args.write_baseline, findings)
        print(f"graftcheck: wrote {len(findings)} fingerprint(s) to "
              f"{args.write_baseline}")
        return 0

    if args.fix:
        diff, fixed = _apply_fixes(findings, abs_root, args.write)
        if diff:
            sys.stdout.write(diff)
        verb = "rewrote" if args.write else "would fix"
        print(f"graftcheck --fix: {verb} {fixed} finding(s) "
              f"({len(findings)} total; non-mechanical findings need "
              f"human fixes)", file=sys.stderr)
        if args.write:
            return 0
        return 1 if fixed else 0

    baseline_path = args.baseline
    if baseline_path is None and os.path.exists("graftcheck_baseline.json"):
        baseline_path = "graftcheck_baseline.json"
    baseline: List[str] = []
    if baseline_path:
        try:
            baseline = load_baseline(baseline_path)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"graftcheck: cannot read baseline: {e}",
                  file=sys.stderr)
            return 2
    covered = [os.path.relpath(os.path.abspath(p), abs_root)
               .replace(os.sep, "/") for p in paths]
    fresh, stale = gate(findings, baseline, covered)

    report = {
        "findings": [f.to_json() for f in fresh],
        "baselined": len(findings) - len(fresh),
        "stale_baseline": stale,
        "rulestamp": RULESTAMP,
        "clean": not (fresh or stale),
        #: per-rule + per-phase wall breakdown — the CI budget evidence
        #: (empty phases on a cache replay)
        "wall": timings,
    }
    if args.json_out:
        try:
            with open(args.json_out, "w", encoding="utf-8") as f:
                json.dump(report, f, indent=1)
                f.write("\n")
        except OSError as e:
            print(f"graftcheck: cannot write --json-out: {e}",
                  file=sys.stderr)
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        for f in fresh:
            print(f.render())
        for fp in stale:
            print(f"graftcheck: STALE baseline entry (fixed finding must "
                  f"leave the baseline): {fp}")
        n_base = len(findings) - len(fresh)
        status = "clean" if not (fresh or stale) else "FAIL"
        print(f"graftcheck: {status} — {len(fresh)} finding(s)"
              + (f", {n_base} baselined" if n_base else "")
              + (f", {len(stale)} stale baseline entr"
                 + ("y" if len(stale) == 1 else "ies") if stale else ""))
    return 1 if (fresh or stale) else 0
