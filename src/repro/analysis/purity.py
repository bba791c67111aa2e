"""Rule family 1: JAX purity / tracing / PRNG discipline.

Applies to every function reachable from a trace entry point
(``jax.jit`` / ``shard_map`` / ``pl.pallas_call`` — see
``repro.analysis.callgraph``):

* ``jax-host-time``   — ``time.time()`` and friends freeze at trace time;
  so does a host span (``repro.obs.span``, ``jax.profiler
  .TraceAnnotation``), which fires once, while tracing.
* ``jax-host-random`` — ``np.random`` / stdlib ``random`` is invisible to
  jax's functional PRNG: the draw happens once, at trace time.
* ``jax-host-sync``   — ``.item()`` / ``float(x)`` / ``np.asarray(x)`` on
  a traced value either aborts tracing (ConcretizationTypeError) or, on
  values threaded out of the region, forces a device round-trip.
* ``prng-constant-key`` — ``jax.random.PRNGKey(<literal>)`` inside traced
  code: every trace re-derives the same stream.  Keys must enter as
  parameters or derive via ``split`` / ``fold_in``.
* ``prng-key-reuse``  — the same key variable fed to two sampling calls
  yields bit-identical draws; re-split between uses.

One rule deliberately reaches OUTSIDE traced code:

* ``jax-blocking-sync`` — ``float(x)`` / ``x.item()`` where ``x`` was
  just returned by a jitted callable.  Legal, but it blocks the host on
  device compute at that exact line; hot paths should defer the
  materialization (store the device value, convert when observed).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from repro.analysis.callgraph import FunctionInfo, TreeIndex, dotted
from repro.analysis.findings import Finding

#: jax.random samplers that CONSUME a key (first positional argument).
SAMPLERS = {
    "normal", "uniform", "choice", "bernoulli", "categorical",
    "permutation", "randint", "truncated_normal", "gumbel",
    "exponential", "poisson", "gamma", "beta", "laplace", "rademacher",
    "bits", "ball", "dirichlet",
}
#: key DERIVATIONS — consume a key but return fresh ones; not "reuse".
DERIVERS = {"split", "fold_in", "clone", "key_data", "wrap_key_data"}

_TIME_CALLS = {"time.time", "time.perf_counter", "time.monotonic",
               "time.process_time", "time.time_ns",
               "datetime.datetime.now", "datetime.datetime.utcnow"}


def _span_heads(mi) -> Set[str]:
    """Dotted names that open a host profiler span in this module."""
    heads = {"jax.profiler.TraceAnnotation", "repro.obs.span"}
    for alias, mod in mi.import_modules.items():
        if mod == "jax":
            heads.add(f"{alias}.profiler.TraceAnnotation")
        elif mod == "jax.profiler":
            heads.add(f"{alias}.TraceAnnotation")
        elif mod == "repro.obs":
            heads.add(f"{alias}.span")
    for local, origin in mi.import_names.items():
        if origin in (("jax.profiler", "TraceAnnotation"),
                      ("repro.obs", "span")):
            heads.add(local)
        elif origin == ("jax", "profiler"):
            heads.add(f"{local}.TraceAnnotation")
        elif origin == ("repro", "obs"):
            heads.add(f"{local}.span")
    return heads


def _numpy_aliases(mi) -> Set[str]:
    out = {alias for alias, mod in mi.import_modules.items()
           if mod in ("numpy", "np")}
    return out or {"np", "numpy"}


def _stdlib_random_aliases(mi) -> Set[str]:
    return {alias for alias, mod in mi.import_modules.items()
            if mod == "random"}


def _jax_random_heads(mi) -> Set[str]:
    """Dotted prefixes that mean jax.random in this module."""
    heads = {"jax.random"}
    for alias, mod in mi.import_modules.items():
        if mod == "jax":
            heads.add(f"{alias}.random")
        if mod == "jax.random":
            heads.add(alias)
    for local, (modpath, orig) in mi.import_names.items():
        if modpath == "jax" and orig == "random":
            heads.add(local)
    return heads


def _finding(fi: FunctionInfo, rule: str, line: int, msg: str) -> Finding:
    src_lines = fi.module.source.splitlines()
    text = src_lines[line - 1].strip() if 0 < line <= len(src_lines) else ""
    return Finding(rule=rule, path=fi.module.rel, line=line, message=msg,
                   symbol=fi.qualname, source=text)


def _check_traced_function(fi: FunctionInfo) -> List[Finding]:
    mi = fi.module
    np_aliases = _numpy_aliases(mi)
    rnd_aliases = _stdlib_random_aliases(mi)
    jr_heads = _jax_random_heads(mi)
    span_heads = _span_heads(mi)
    findings: List[Finding] = []
    # static argnames are concrete Python values at trace time — a
    # float()/np.asarray() on them is not a host sync
    static = fi.static_argnames

    # linear scan in source order so reassignments reset key tracking
    calls = [n for n in ast.walk(fi.node) if isinstance(n, ast.Call)]
    calls.sort(key=lambda n: (n.lineno, n.col_offset))
    assigns = [n for n in ast.walk(fi.node)
               if isinstance(n, (ast.Assign, ast.AugAssign))]
    # name -> line of last sampler use (for prng-key-reuse)
    key_used_at: Dict[str, int] = {}

    def reset_names_assigned_before(line: int) -> None:
        for a in assigns:
            if a.lineno <= line:
                targets = (a.targets if isinstance(a, ast.Assign)
                           else [a.target])
                for t in targets:
                    for sub in ast.walk(t):
                        if isinstance(sub, ast.Name):
                            key_used_at.pop(sub.id, None)

    last_seen_line = 0
    for call in calls:
        head = dotted(call.func)
        line = call.lineno

        # host clocks
        if head in _TIME_CALLS or (head and head.split(".")[-1] in
                                   ("time", "perf_counter", "monotonic")
                                   and head.split(".")[0] == "time"):
            findings.append(_finding(
                fi, "jax-host-time", line,
                f"'{head}()' in traced code — the clock value freezes at "
                f"trace time; thread timestamps in as arguments"))
            continue
        if head is None:
            continue
        if head in span_heads:
            findings.append(_finding(
                fi, "jax-host-time", line,
                f"'{head}(...)' in traced code — a host span opens once, "
                f"at trace time, and times nothing that runs; open it "
                f"around the call into the jitted function"))
            continue
        parts = head.split(".")

        # host randomness: np.random.* / stdlib random.*
        if parts[0] in np_aliases and len(parts) >= 2 \
                and parts[1] == "random":
            findings.append(_finding(
                fi, "jax-host-random", line,
                f"'{head}()' in traced code — host RNG draws once at "
                f"trace time; use jax.random with an explicit key"))
            continue
        if parts[0] in rnd_aliases and len(parts) == 2:
            findings.append(_finding(
                fi, "jax-host-random", line,
                f"stdlib '{head}()' in traced code — use jax.random"))
            continue

        # host syncs on traced values
        if parts[-1] == "item" and len(parts) >= 2:
            findings.append(_finding(
                fi, "jax-host-sync", line,
                "'.item()' in traced code aborts tracing / syncs the "
                "device; keep the value on device"))
            continue
        if head == "float" and call.args \
                and not isinstance(call.args[0], ast.Constant) \
                and not (isinstance(call.args[0], ast.Name)
                         and call.args[0].id in static):
            findings.append(_finding(
                fi, "jax-host-sync", line,
                "'float(...)' on a traced value concretizes it; use "
                "jnp/astype inside traced code"))
            continue
        if parts[0] in np_aliases and parts[-1] == "asarray" \
                and not (call.args
                         and isinstance(call.args[0], ast.Name)
                         and call.args[0].id in static):
            findings.append(_finding(
                fi, "jax-host-sync", line,
                "'np.asarray(...)' in traced code pulls the value to "
                "host; use jnp.asarray"))
            continue

        # PRNG key discipline
        jr_parent = ".".join(parts[:-1])
        if jr_parent in jr_heads and parts[-1] == "PRNGKey":
            findings.append(_finding(
                fi, "prng-constant-key", line,
                "PRNGKey(...) constructed inside traced code — every "
                "trace re-derives the same stream; pass the key in as a "
                "parameter (or derive it via split/fold_in)"))
            continue
        if jr_parent in jr_heads and parts[-1] in SAMPLERS:
            reset_names_assigned_before(max(last_seen_line, 0))
            last_seen_line = line
            if call.args and isinstance(call.args[0], ast.Name):
                name = call.args[0].id
                # a reassignment between the two uses clears the name
                for a in assigns:
                    if key_used_at.get(name, 0) < a.lineno <= line:
                        targets = (a.targets if isinstance(a, ast.Assign)
                                   else [a.target])
                        for t in targets:
                            for sub in ast.walk(t):
                                if isinstance(sub, ast.Name) \
                                        and sub.id == name:
                                    key_used_at.pop(name, None)
                if name in key_used_at:
                    findings.append(_finding(
                        fi, "prng-key-reuse", line,
                        f"key '{name}' already consumed by a sampler at "
                        f"line {key_used_at[name]} — identical streams; "
                        f"split or fold_in between uses"))
                key_used_at[name] = line
            # constant key fed straight into a sampler
            if call.args and isinstance(call.args[0], ast.Call):
                inner = dotted(call.args[0].func)
                if inner and inner.split(".")[-1] == "PRNGKey":
                    findings.append(_finding(
                        fi, "prng-constant-key", line,
                        "sampler fed a literal PRNGKey(...) — the key "
                        "must originate from a parameter or split/"
                        "fold_in"))
    return findings


def _check_blocking_sync(fi: FunctionInfo, tree: TreeIndex) -> List[Finding]:
    """float(x)/.item() on names assigned from jitted calls (any code)."""
    mi = fi.module
    jit_results: Dict[str, int] = {}       # name -> assignment line
    findings: List[Finding] = []

    def flag_call(node: ast.Call) -> None:
        head = dotted(node.func)
        if head == "float" and node.args \
                and isinstance(node.args[0], ast.Name) \
                and node.args[0].id in jit_results:
            findings.append(_finding(
                fi, "jax-blocking-sync", node.lineno,
                f"float({node.args[0].id}) blocks on the jitted call "
                f"at line {jit_results[node.args[0].id]}; defer the "
                f"host sync (store the device value, materialize "
                f"when observed)"))
        elif head and head.split(".")[-1] == "item" \
                and len(head.split(".")) == 2 \
                and head.split(".")[0] in jit_results:
            name = head.split(".")[0]
            findings.append(_finding(
                fi, "jax-blocking-sync", node.lineno,
                f"{name}.item() blocks on the jitted call at line "
                f"{jit_results[name]}; defer the host sync"))

    stmts = [n for n in ast.walk(fi.node)
             if isinstance(n, (ast.Assign, ast.Call))]
    stmts.sort(key=lambda n: (n.lineno, n.col_offset))
    # calls that are the RHS of an assignment are handled inside the
    # Assign branch (RHS evaluates before the binding), not standalone
    assign_rhs = {id(n.value) for n in stmts if isinstance(n, ast.Assign)}
    for node in stmts:
        if isinstance(node, ast.Assign):
            if isinstance(node.value, ast.Call):
                flag_call(node.value)
            jitted = False
            if isinstance(node.value, ast.Call):
                head = dotted(node.value.func)
                callee = tree.resolve(mi, fi, head) if head else None
                jitted = ((callee is not None and callee.is_root)
                          or bool(head
                                  and tree.is_jit_wrapped_call(mi, head)))
            for tgt in node.targets:
                names = ([tgt] if isinstance(tgt, ast.Name)
                         else [e for e in getattr(tgt, "elts", [])
                               if isinstance(e, ast.Name)])
                for n in names:
                    if jitted:
                        jit_results[n.id] = node.lineno
                    else:
                        jit_results.pop(n.id, None)
        elif isinstance(node, ast.Call) and id(node) not in assign_rhs:
            flag_call(node)
    return findings


def check(tree: TreeIndex) -> List[Finding]:
    findings: List[Finding] = []
    traced = tree.traced_functions()
    for fi in sorted(traced, key=lambda f: (f.module.rel, f.qualname)):
        findings.extend(_check_traced_function(fi))
    traced_ids = {id(f) for f in traced}
    for mi in tree.modules.values():
        for fi in mi.functions.values():
            if id(fi) not in traced_ids:
                findings.extend(_check_blocking_sync(fi, tree))
    return findings
