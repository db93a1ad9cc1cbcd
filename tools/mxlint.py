#!/usr/bin/env python
"""mxlint — framework-aware AST lint for the mxnet_tpu library itself.

The third leg of the analysis subsystem (graph verifier / sync-hazard
sanitizer / source linter): rules that encode *this framework's* contracts,
which generic linters cannot know about.

Rules
-----
bare-except       ``except:`` swallows KeyboardInterrupt/SystemExit and
                  every deferred engine error — name the exception type.
host-sync         ``.asnumpy()`` / ``.asscalar()`` / ``.item()`` in library
                  code — each is a device round-trip and splits any live
                  bulk segment; hot paths must stay async.
raw-jit           a direct ``jax.jit(`` call outside ``compile.py`` —
                  every compile must go through the
                  unified compile service (``mxnet_tpu.compile.jit``) so
                  it gets the canonical cache key, the persistent on-disk
                  cache, AOT warmup and the per-site hit/miss metrics;
                  a raw jit site is invisible to all four.
unseeded-random   module-level ``np.random.*`` draws bypass the seeded
                  stream (``mxnet_tpu.random`` / an explicit RandomState):
                  nondeterminism ``mx.random.seed`` cannot control.
no-schema-doc     an op registered via ``@register(...)`` without a
                  docstring — the reflected schema dump (``op_schemas``,
                  opperf arg synthesis, doc generation) has nothing to show.
unused-import     module-level import never referenced in the file.
mutable-default   ``def f(x=[] / {} / set())`` — shared-state bug class.
unbounded-sync    a bare ``.join()`` / ``.block_until_ready()`` in library
                  code — an unbounded blocking wait that bypasses the
                  watchdog wrappers (``mxnet_tpu.watchdog.sync``); a wedge
                  behind it stalls the process forever with no crash
                  bundle. ``watchdog.py`` itself is exempt (it IS the
                  wrapper home).
partition-spec-literal
                  a hand-written PartitionSpec (or ``mesh.sharding(...)``)
                  axis string outside ``parallel/`` that is not in the
                  canonical mesh-axis vocabulary (dp/pp/tp/sp/ep —
                  ``parallel/mesh.py AXIS_ORDER``): an off-vocabulary
                  axis silently replicates on every standard mesh, the
                  exact bug class the distcheck sharding verifier exists
                  for. Keep axis names in the vocabulary (or route
                  through ``parallel/``).
print-call        a bare ``print()`` inside the ``mxnet_tpu/`` package:
                  library state must flow through structured surfaces —
                  ``mxnet_tpu.log`` (leveled, capturable) or
                  ``mxnet_tpu.telemetry`` (scrapeable) — never stdout a
                  fleet operator cannot collect or silence. ``tools/``,
                  tests, and ``if __name__ == "__main__"`` demo blocks
                  are exempt; the few user-facing table printers that ARE
                  an API contract (``Block.summary``,
                  ``visualization.print_summary``) are baselined.
raw-pallas-call   a direct ``pl.pallas_call(...)`` outside
                  ``mxnet_tpu/kernels/`` — hand-rolled Pallas call sites
                  bypass the kernel registry, so they get no autotuned
                  per-shape dispatch, no XLA fallback when Pallas is
                  unavailable, and no fallback/dispatch telemetry.
                  Did you mean: implement the kernel in
                  ``mxnet_tpu/kernels/``, wire it with
                  ``kernels.register_kernel(...)`` and call it through
                  ``kernels.dispatch(family, ...)``.
serving-blocking-call
                  a blocking call in ``serving/`` code outside a
                  ``watchdog.sync(...)`` span: device syncs
                  (``wait_to_read``/``waitall``/``asnumpy``/
                  ``block_until_ready``/...) and unbounded waits
                  (zero-argument ``.join()``/``.result()``/``.get()``/
                  ``.wait()``/``.acquire()``). The serving contract is
                  bounded tail latency BY CONSTRUCTION — every wait must
                  carry a timeout or run under a watchdog deadline, so a
                  wedged device yields a crash bundle + StallError, never
                  a hung server. Callables passed to ``*.sync(...)``
                  (inline lambdas or local functions by name) are exempt:
                  the sync IS their deadline.

Baseline workflow
-----------------
Existing findings live in ``tools/mxlint_baseline.txt`` as
``<rule> <path> <count>  # justification`` lines; a run fails ONLY when a
(rule, file) pair exceeds its baselined count, so CI is green on legacy
debt but red on new violations. Shrink the baseline as debt burns down
(`--write-baseline` regenerates it; stale surplus entries are reported).

Suppression: a ``# noqa`` or ``# noqa: <rule>`` comment on the offending
line, for violations that are deliberate (e.g. the one blessed host sync
inside ``asnumpy`` itself).

Usage
-----
    python tools/mxlint.py mxnet_tpu                # gate vs baseline
    python tools/mxlint.py --no-baseline mxnet_tpu  # every finding
    python tools/mxlint.py --write-baseline mxnet_tpu
"""
from __future__ import annotations

import argparse
import ast
import os
import sys
from collections import Counter

DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "mxlint_baseline.txt")

RULES = ("bare-except", "host-sync", "raw-jit",
         "unseeded-random", "no-schema-doc", "unused-import",
         "mutable-default", "unbounded-sync", "partition-spec-literal",
         "serving-blocking-call", "print-call", "raw-pallas-call",
         "lock-order", "shared-state", "torn-file")

# the three concurrency rules delegate to the analyzer's static passes
# (analysis/concur.py, loaded standalone so linting stays jax-free)
_CONCUR_RULEMAP = {
    "lock-order-cycle": "lock-order",
    "unlocked-shared-state": "shared-state",
    "torn-file-write": "torn-file",
    "torn-tmp-name": "torn-file",
    "torn-read": "torn-file",
}

# serving/ blocking-call vocabulary: device syncs (flagged regardless of
# arguments) and waits that are unbounded only in their zero-arg form
_SERVING_BLOCKING = {"wait_to_read", "wait_to_write", "waitall", "asnumpy",
                     "asscalar", "block_until_ready", "item"}
_SERVING_UNBOUNDED = {"join", "result", "get", "wait", "acquire"}

_SYNC_METHODS = {"asnumpy", "asscalar"}
# canonical mesh-axis vocabulary — keep in sync with
# mxnet_tpu/parallel/mesh.py AXIS_ORDER
_MESH_AXES = {"dp", "pp", "tp", "sp", "ep"}
_NP_RANDOM_FNS = {
    "rand", "randn", "randint", "random", "random_sample", "ranf", "sample",
    "uniform", "normal", "standard_normal", "choice", "shuffle",
    "permutation", "beta", "binomial", "exponential", "gamma", "poisson",
    "multinomial", "bytes",
}
_NP_ALIASES = {"np", "_np", "onp", "_onp", "numpy"}


class Finding:
    __slots__ = ("path", "line", "col", "rule", "message")

    def __init__(self, path, line, col, rule, message):
        self.path = path
        self.line = line
        self.col = col
        self.rule = rule
        self.message = message

    def __str__(self):
        return f"{self.path}:{self.line}:{self.col}: " \
               f"{self.rule}: {self.message}"


def _dotted(node):
    """'jax.experimental.shard_map' for a nested Attribute/Name chain, or
    None when the chain has non-name parts (calls, subscripts)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _Linter(ast.NodeVisitor):
    def __init__(self, path, rel, source):
        self.path = path
        self.rel = rel
        self.findings = []
        self.lines = source.splitlines()
        self.is_init = os.path.basename(path) == "__init__.py"
        self.is_watchdog = os.path.basename(path) == "watchdog.py"
        # compile.py IS the service — the one home of raw jax.jit
        self.is_compile = os.path.basename(path) == "compile.py"
        # parallel/ is the home of the sharding vocabulary itself
        self.is_parallel = "/parallel/" in rel.replace(os.sep, "/")
        # serving/ code must never wait unboundedly outside watchdog.sync
        self.is_serving = "serving" in rel.replace(os.sep, "/").split("/")[:-1]
        # kernels/ is the one home of raw pl.pallas_call sites
        self.is_kernels = "kernels" in rel.replace(os.sep, "/").split("/")[:-1]
        self._serving_pending = []  # (node, message) resolved in finish()
        # print-call applies only inside the mxnet_tpu package (tools/,
        # tests and standalone scripts print by design)
        self.in_package = rel.replace(os.sep, "/").split("/")[0] \
            == "mxnet_tpu"
        self._main_intervals = []  # `if __name__ == "__main__"` bodies
        self.pspec_aliases = set()  # local names bound to PartitionSpec
        # module-level import bookkeeping for unused-import
        self.imports = {}   # local name -> (lineno, col, "import x" repr)
        self.used = set()
        self.dunder_all = set()

    # ------------------------------------------------------------ helpers --
    def add(self, node, rule, message):
        line = getattr(node, "lineno", 1)
        text = self.lines[line - 1] if line <= len(self.lines) else ""
        if "# noqa" in text:
            tail = text.split("# noqa", 1)[1]
            if not tail.startswith(":") or rule in tail:
                return
        self.findings.append(Finding(
            self.rel, line, getattr(node, "col_offset", 0), rule, message))

    # ------------------------------------------------------------- visits --
    def visit_ExceptHandler(self, node):
        if node.type is None:
            self.add(node, "bare-except",
                     "bare 'except:' also catches KeyboardInterrupt/"
                     "SystemExit and deferred engine errors; name the "
                     "exception type")
        self.generic_visit(node)

    def visit_If(self, node):
        # `if __name__ == "__main__":` demo/smoke blocks are print-call
        # exempt (they run as scripts, not as library code)
        t = node.test
        if isinstance(t, ast.Compare) and len(t.ops) == 1 \
                and isinstance(t.ops[0], ast.Eq):
            sides = [t.left] + list(t.comparators)
            names = {s.id for s in sides if isinstance(s, ast.Name)}
            consts = {s.value for s in sides
                      if isinstance(s, ast.Constant)}
            if "__name__" in names and "__main__" in consts:
                self._main_intervals.append(
                    (node.lineno, getattr(node, "end_lineno",
                                          node.lineno)))
        self.generic_visit(node)

    def visit_Call(self, node):
        func = node.func
        if self.in_package and isinstance(func, ast.Name) \
                and func.id == "print":
            line = getattr(node, "lineno", 1)
            if not any(lo <= line <= hi
                       for lo, hi in self._main_intervals):
                self.add(node, "print-call",
                         "bare print() in library code goes to a stdout "
                         "no fleet operator collects; use mxnet_tpu.log "
                         "(leveled logging) or mxnet_tpu.telemetry "
                         "(metrics/flight recorder) — tools/, tests and "
                         "__main__ blocks are exempt")
        if isinstance(func, ast.Attribute):
            if func.attr in _SYNC_METHODS and not node.args \
                    and not node.keywords:
                self.add(node, "host-sync",
                         f".{func.attr}() is a blocking device->host "
                         "round-trip (and splits any live bulk segment); "
                         "library hot paths must stay async")
            if not self.is_watchdog:
                # thread.join() takes no args; str.join always takes one —
                # the zero-arg form is the unbounded-wait shape
                if (func.attr == "block_until_ready"
                        or (func.attr == "join" and not node.args
                            and not node.keywords)):
                    self.add(node, "unbounded-sync",
                             f".{func.attr}() blocks unboundedly and "
                             "bypasses the watchdog — route through "
                             "mxnet_tpu.watchdog.sync so a wedge raises "
                             "StallError with a crash bundle")
            if func.attr == "pallas_call" and not self.is_kernels:
                self.add(node, "raw-pallas-call",
                         "raw pl.pallas_call outside mxnet_tpu/kernels/ "
                         "bypasses the kernel registry (no autotuned "
                         "dispatch, no XLA fallback, no telemetry) — did "
                         "you mean kernels.register_kernel(...) + "
                         "kernels.dispatch(family, ...)?")
            chain = _dotted(func)
            if chain is not None:
                self._check_np_random(node, chain)
            if self.is_serving:
                self._check_serving_blocking(node, func)
        self._check_partition_spec(node)
        self.generic_visit(node)

    def _check_serving_blocking(self, node, func):
        attr = func.attr
        unbounded = (attr in _SERVING_UNBOUNDED and not node.args
                     and not node.keywords)
        if attr in _SERVING_BLOCKING:
            why = f".{attr}() blocks on the device"
        elif unbounded:
            why = f"zero-argument .{attr}() waits unboundedly"
        else:
            return
        self._serving_pending.append((node, (
            f"{why}; serving code is bounded-tail-latency by construction "
            "— run it inside watchdog.sync('serving.batch', ...) or pass "
            "a timeout")))

    def _check_partition_spec(self, node):
        if self.is_parallel:
            return
        func = node.func
        chain = _dotted(func) or ""
        is_spec_site = (
            (isinstance(func, ast.Name) and func.id in self.pspec_aliases)
            or chain.endswith(".PartitionSpec")
            or (isinstance(func, ast.Attribute) and func.attr == "sharding"))
        if not is_spec_site:
            return
        for arg in node.args:
            elts = arg.elts if isinstance(arg, (ast.Tuple, ast.List)) \
                else [arg]
            for elt in elts:
                if isinstance(elt, ast.Constant) \
                        and isinstance(elt.value, str) \
                        and elt.value not in _MESH_AXES:
                    import difflib

                    close = difflib.get_close_matches(
                        elt.value, sorted(_MESH_AXES), n=1)
                    hint = f" (did you mean {close[0]!r}?)" if close else ""
                    self.add(elt, "partition-spec-literal",
                             f"PartitionSpec axis {elt.value!r} is not in "
                             "the canonical mesh-axis vocabulary "
                             f"{sorted(_MESH_AXES)}{hint}; off-vocabulary "
                             "axes silently replicate on standard meshes "
                             "— use a canonical axis or keep the spec in "
                             "parallel/")

    def _check_np_random(self, node, chain):
        parts = chain.split(".")
        if len(parts) == 3 and parts[0] in _NP_ALIASES \
                and parts[1] == "random" and parts[2] in _NP_RANDOM_FNS:
            self.add(node, "unseeded-random",
                     f"{chain}() draws from numpy's global unseeded stream; "
                     "use mxnet_tpu.random (device ops) or a RandomState/"
                     "default_rng threaded from a seed (host-side shuffles)")

    def visit_Attribute(self, node):
        if not self.is_compile and node.attr == "jit":
            chain = _dotted(node)
            if chain is not None and chain.split(".")[0] == "jax":
                self.add(node, "raw-jit",
                         f"{chain} bypasses the unified compile service — "
                         "use mxnet_tpu.compile.jit(fn, site=..., "
                         "token=...) so this executable gets the "
                         "canonical cache key, disk persistence, AOT "
                         "warmup and cache metrics")
        self._mark_used(node)
        # do NOT generic_visit: _mark_used consumed the name chain

    def visit_Name(self, node):
        self.used.add(node.id)

    def _mark_used(self, node):
        while isinstance(node, ast.Attribute):
            node = node.value
        if isinstance(node, ast.Name):
            self.used.add(node.id)
        else:
            self.generic_visit(node)

    def visit_Import(self, node):
        self._collect_import(node,
                             ((a.asname or a.name.split(".")[0], a.name)
                              for a in node.names))

    def visit_ImportFrom(self, node):
        mod = node.module or ""
        if mod == "__future__":
            return
        if mod == "jax.sharding":
            for a in node.names:
                if a.name == "PartitionSpec":
                    self.pspec_aliases.add(a.asname or a.name)
        if not self.is_compile and mod == "jax":
            for a in node.names:
                if a.name == "jit":
                    self.add(node, "raw-jit",
                             "'from jax import jit' bypasses the unified "
                             "compile service; use mxnet_tpu.compile.jit")
        self._collect_import(node, ((a.asname or a.name, a.name)
                                    for a in node.names))

    def _collect_import(self, node, names):
        if node.col_offset != 0 or self.is_init:
            # only module-level imports outside __init__ re-export files
            return
        for local, orig in names:
            if local == "*":
                continue
            self.imports.setdefault(local, (node, orig))

    def visit_FunctionDef(self, node, _async=False):
        self._check_register_doc(node)
        self._check_mutable_defaults(node)
        self.generic_visit(node)

    def visit_AsyncFunctionDef(self, node):
        self.visit_FunctionDef(node, _async=True)

    def _check_register_doc(self, node):
        for deco in node.decorator_list:
            call = deco if isinstance(deco, ast.Call) else None
            target = call.func if call else deco
            name = target.attr if isinstance(target, ast.Attribute) \
                else getattr(target, "id", None)
            if name == "register" and ast.get_docstring(node) is None:
                self.add(node, "no-schema-doc",
                         f"op function {node.name!r} is registered without "
                         "a docstring; the reflected schema dump "
                         "(op_schemas/opperf/docs) has nothing to show")

    def _check_mutable_defaults(self, node):
        defaults = list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None]
        for d in defaults:
            if isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                    isinstance(d, ast.Call)
                    and isinstance(d.func, ast.Name)
                    and d.func.id in ("list", "dict", "set")):
                self.add(d, "mutable-default",
                         "mutable default argument is shared across calls; "
                         "default to None (or a tuple) instead")

    def visit_Assign(self, node):
        for t in node.targets:
            if isinstance(t, ast.Name) and t.id == "__all__" \
                    and isinstance(node.value, (ast.List, ast.Tuple)):
                for elt in node.value.elts:
                    if isinstance(elt, ast.Constant) \
                            and isinstance(elt.value, str):
                        self.dunder_all.add(elt.value)
        self.generic_visit(node)

    # ------------------------------------------------------------- finish --
    def _sync_exempt_intervals(self, tree):
        """Line intervals covered by a watchdog deadline: every argument
        of a ``*.sync(...)`` call after the point name (inline lambdas),
        plus the bodies of local functions passed to one by name."""
        intervals, names = [], set()
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "sync"):
                continue
            for arg in node.args[1:]:
                if isinstance(arg, ast.Name):
                    names.add(arg.id)
                else:
                    intervals.append((arg.lineno,
                                      getattr(arg, "end_lineno",
                                              arg.lineno)))
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in names:
                intervals.append((node.lineno,
                                  getattr(node, "end_lineno", node.lineno)))
        return intervals

    def finish(self, tree):
        # names used in nested strings (getattr-style) are not tracked —
        # unused-import stays conservative: report only plain never-seen
        # names, skipping noqa'd lines via add()
        for local, (node, orig) in self.imports.items():
            if local in self.used or local in self.dunder_all:
                continue
            self.add(node, "unused-import",
                     f"imported name {local!r} "
                     f"({orig}) is never used in this module")
        if self._serving_pending:
            exempt = self._sync_exempt_intervals(tree)
            for node, message in self._serving_pending:
                line = getattr(node, "lineno", 1)
                if any(lo <= line <= hi for lo, hi in exempt):
                    continue
                self.add(node, "serving-blocking-call", message)
        return self.findings


def lint_file(path, rel):
    try:
        with open(path, encoding="utf-8") as f:
            source = f.read()
    except (OSError, UnicodeDecodeError) as exc:
        return [Finding(rel, 1, 0, "bare-except", f"unreadable: {exc}")]
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as exc:
        return [Finding(rel, exc.lineno or 1, 0, "bare-except",
                        f"syntax error: {exc.msg}")]
    linter = _Linter(path, rel, source)
    linter.visit(tree)
    return linter.finish(tree)


def iter_py_files(targets, root):
    for target in targets:
        target = os.path.join(root, target) if not os.path.isabs(target) \
            else target
        if os.path.isfile(target):
            yield target
            continue
        for dirpath, dirnames, filenames in os.walk(target):
            dirnames[:] = [d for d in sorted(dirnames)
                           if d not in ("__pycache__", ".git")]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


_concur_mod = None


def _load_concur():
    """The concurrency analyzer, loaded standalone by file path: its
    static passes are stdlib-only, so linting never imports the jax-heavy
    package."""
    global _concur_mod
    if _concur_mod is None:
        import importlib.util

        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "mxnet_tpu", "analysis", "concur.py")
        spec = importlib.util.spec_from_file_location("_mxlint_concur",
                                                      path)
        _concur_mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(_concur_mod)
    return _concur_mod


def _concur_findings(paths, root):
    """Concurrency passes 1-3 over the lint target set, mapped to the
    lock-order / shared-state / torn-file rules (honouring `# noqa`)."""
    try:
        concur = _load_concur()
    except (OSError, ImportError):
        return []
    findings = []
    line_cache = {}
    for issue in concur.run_static(files=list(paths), root=root):
        rule = _CONCUR_RULEMAP.get(issue.code)
        if rule is None:
            continue
        rel, _, line_s = issue.node.rpartition(":")
        line = int(line_s) if line_s.isdigit() else 1
        if rel not in line_cache:
            try:
                with open(os.path.join(root, rel), encoding="utf-8") as f:
                    line_cache[rel] = f.read().splitlines()
            except (OSError, UnicodeDecodeError):
                line_cache[rel] = []
        lines = line_cache[rel]
        text = lines[line - 1] if line <= len(lines) else ""
        if "# noqa" in text:
            tail = text.split("# noqa", 1)[1]
            if not tail.startswith(":") or rule in tail:
                continue
        where = f" [{issue.op}]" if issue.op else ""
        findings.append(Finding(rel, line, 0, rule,
                                f"({issue.code}){where} {issue.message}"))
    return findings


def run(targets, root=None):
    """Lint `targets` (files/dirs); returns findings with root-relative
    paths."""
    root = root or os.getcwd()
    findings = []
    paths = list(iter_py_files(targets, root))
    for path in paths:
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        findings.extend(lint_file(path, rel))
    findings.extend(_concur_findings(paths, root))
    return findings


# ------------------------------------------------------------- baseline ----

def load_baseline(path):
    """{(rule, relpath): allowed_count} from the checked-in baseline."""
    allowed = {}
    if not os.path.exists(path):
        return allowed
    with open(path, encoding="utf-8") as f:
        for raw in f:
            line = raw.split("#", 1)[0].strip()
            if not line:
                continue
            try:
                rule, rel, count = line.split()
                allowed[(rule, rel)] = int(count)
            except ValueError:
                print(f"mxlint: malformed baseline line ignored: {raw!r}",
                      file=sys.stderr)
    return allowed


def write_baseline(path, findings):
    counts = Counter((f.rule, f.path) for f in findings)
    with open(path, "w", encoding="utf-8") as f:
        f.write("# mxlint baseline — legacy findings tolerated by the CI "
                "gate.\n# Format: <rule> <path> <count>  [# justification]"
                "\n# Regenerate: python tools/mxlint.py --write-baseline "
                "mxnet_tpu\n")
        for (rule, rel), n in sorted(counts.items()):
            f.write(f"{rule} {rel} {n}\n")


def compare(findings, allowed):
    """(new, fixed): findings beyond baseline counts, and baseline surplus
    that can now be shrunk."""
    counts = Counter((f.rule, f.path) for f in findings)
    new = []
    for key, n in sorted(counts.items()):
        extra = n - allowed.get(key, 0)
        if extra > 0:
            rule, rel = key
            culprits = [f for f in findings if (f.rule, f.path) == key]
            new.append((rule, rel, extra, culprits))
    fixed = [(rule, rel, allowed[(rule, rel)] - counts.get((rule, rel), 0))
             for (rule, rel) in sorted(allowed)
             if allowed[(rule, rel)] > counts.get((rule, rel), 0)]
    return new, fixed


# ------------------------------------------------------------------ main ---

def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="mxlint", description="framework-aware lint for mxnet_tpu")
    ap.add_argument("targets", nargs="+", help="files or directories")
    ap.add_argument("--root", default=None,
                    help="repo root for relative paths (default: cwd, or "
                         "the repo containing this script)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline file (default: tools/mxlint_baseline.txt)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding; exit 1 if any")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate the baseline from current findings")
    ap.add_argument("--rule", action="append", choices=RULES,
                    help="restrict to specific rule(s)")
    args = ap.parse_args(argv)

    root = args.root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    findings = run(args.targets, root=root)
    if args.rule:
        findings = [f for f in findings if f.rule in args.rule]

    if args.write_baseline:
        write_baseline(args.baseline, findings)
        print(f"mxlint: baseline written to {args.baseline} "
              f"({len(findings)} findings)")
        return 0

    if args.no_baseline:
        for f in findings:
            print(f)
        print(f"mxlint: {len(findings)} finding"
              f"{'s' if len(findings) != 1 else ''}")
        return 1 if findings else 0

    allowed = load_baseline(args.baseline)
    new, fixed = compare(findings, allowed)
    for rule, rel, extra, culprits in new:
        print(f"mxlint: {rel}: {extra} new {rule} violation"
              f"{'s' if extra != 1 else ''} "
              f"(baseline {allowed.get((rule, rel), 0)}, "
              f"now {len(culprits)}):")
        for f in culprits:
            print(f"  {f}")
    for rule, rel, surplus in fixed:
        print(f"mxlint: note: baseline for ({rule}, {rel}) can shrink by "
              f"{surplus} — run --write-baseline to lock in the burn-down")
    if new:
        print("mxlint: FAIL — fix the new violations, add '# noqa: <rule>' "
              "with cause, or (last resort) re-baseline with a "
              "justification comment")
        return 1
    print(f"mxlint: OK ({len(findings)} findings, all within baseline)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
