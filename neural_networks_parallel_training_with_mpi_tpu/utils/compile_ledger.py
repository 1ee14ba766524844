"""Compile-event ledger: one interception seam around every XLA compile.

ROADMAP item 3's complaint is that every layout re-pays compile wiring
at N× cost — but the repo never MEASURED that cost, and the serving
kernel's flagship invariant ("block-table churn never recompiles") was
pinned by counting jit cache entries in one test rather than observed in
production.  This module is the seam both needs: wrap any jitted
callable with :func:`instrument` and, while a :class:`Ledger` is
installed, every NEW argument signature is compiled through the AOT path
(``fn.lower(args).compile()``) with the event recorded to
``compiles.jsonl``:

* module name + which compile this is (``n_compile``),
* the full arg-shape/dtype signature (tree paths → ``dtype[shape]``),
* on a recompile, WHICH signature component changed
  (``changed/added/removed`` — the paged-attention "table churn never
  recompiles" pin becomes a ledger assertion, and a genuine recompile
  names its trigger),
* lower + compile wall time (the N× wiring cost item 3 wants to
  collapse, now quantified per run),
* the lowered module's SHA-256 fingerprint (same program text ⇒ same
  fingerprint — cross-run compile-cache attribution),
* XLA cost analysis (flops, bytes accessed) and the compiler's memory
  count (temporaries, arguments, outputs) where the backend reports them,
* what the program said of itself while it was traced (:func:`note`:
  ``attention``, the resolved implementation and the kernels' tiling).

The compiled executable is cached per signature and reused, so the
ledger observes every compile exactly once and the program runs through
the SAME XLA executable the jit path would build — params are
bitwise-identical ledger-on vs ledger-off (tests/test_trace.py pins it;
PERF.md §6, PR 26, has the host-side cost on the chip).  A call whose signature is already compiled does no
per-leaf Python work (:class:`InstrumentedFn`).  When no ledger is
installed the wrapper is a pass-through to the original jitted
callable: zero behavior change.

Degradation ladder (never break the run for observability):
* callables without ``.lower`` (plain-python wrappers around inner jits)
  record signature events without HLO/cost detail;
* a FAILED AOT dispatch re-raises the original error (donated buffers
  may be gone, and a peer-loss error rewrapped by a retry would dodge
  the CLI's exit-43 classification) and routes LATER calls for that
  signature through the jit path.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .logging import log

__all__ = ["Ledger", "InstrumentedFn", "instrument", "install", "active",
           "note"]


class Ledger:
    """Append-only compile-event sink: a JSONL file (PR 2 writer
    discipline) plus an in-process ``events`` list the pins assert on.
    Identity triple mirrors ``train.trace``: every record carries
    (process_id, run_id, incarnation)."""

    def __init__(self, path: Optional[str], process_id: int = 0,
                 run_id: str = "", incarnation: int = 0):
        self.path = path
        self.events: List[Dict[str, Any]] = []
        self._ident = {"p": int(process_id), "run": str(run_id),
                       "inc": int(incarnation)}
        self._lock = threading.Lock()
        self._f = open(path, "a") if path else None
        # what the program being lowered says of itself (a compile belongs
        # to the thread that asked for it)
        self._lowering = threading.local()

    @contextlib.contextmanager
    def collecting_notes(self):
        """The notes made while the body lowers one program."""
        self._lowering.notes = notes = {}
        try:
            yield notes
        finally:
            self._lowering.notes = None

    def note(self, key: str, value: Any) -> None:
        notes = getattr(self._lowering, "notes", None)
        if notes is not None:
            notes.setdefault(key, value)

    def record(self, rec: Dict[str, Any]) -> None:
        rec = {**rec, **self._ident}
        with self._lock:
            self.events.append(rec)
            if self._f is not None:
                self._f.write(json.dumps(rec) + "\n")
                self._f.flush()

    def events_for(self, name_prefix: str) -> List[Dict[str, Any]]:
        return [e for e in self.events
                if str(e.get("name", "")).startswith(name_prefix)]

    def compile_seconds(self) -> float:
        return sum((e.get("compile_ms") or 0.0) for e in self.events) / 1e3

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None


_ACTIVE: Optional[Ledger] = None


def install(ledger: Optional[Ledger]) -> None:
    global _ACTIVE
    _ACTIVE = ledger


def active() -> Optional[Ledger]:
    return _ACTIVE


def note(key: str, value: Any) -> None:
    """Called by code that runs while a program is traced: put ``key`` on
    the ledger's event for that program (the first value wins, so a choice
    made once a layer is recorded once).  Outside a ledger's compile it
    does nothing."""
    if _ACTIVE is not None:
        _ACTIVE.note(key, value)


# ---------------------------------------------------------------------------
# signatures
# ---------------------------------------------------------------------------

def _leaf_key(x) -> Tuple:
    """Hashable per-leaf cache key: (shape, dtype, weak_type, sharding)
    for array-likes; python scalars key by type (jit traces them as weak
    scalars — the value never affects the compiled program).  The
    sharding term matters: an AOT executable is pinned to the input
    placement it was compiled for, so a same-shaped arg arriving under a
    DIFFERENT sharding must compile fresh — exactly what jit's own cache
    would do — instead of dispatching the stale executable and dying on
    a placement mismatch only when tracing is on."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return ("py", type(x).__name__)
    sharding = getattr(x, "sharding", None)  # None for numpy hosts
    return (tuple(shape), str(dtype),
            bool(getattr(x, "weak_type", False)), sharding)


def _leaf_str(x) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return f"py:{type(x).__name__}"
    return f"{dtype}[{','.join(str(d) for d in shape)}]"


def _signature(args) -> Dict[str, str]:
    """Tree-path → ``dtype[shape]`` over the call's argument tuple — the
    human-readable form recorded in the ledger and diffed on recompile."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(args)[0]
    return {jax.tree_util.keystr(path): _leaf_str(leaf)
            for path, leaf in flat}


def signature_diff(old: Dict[str, str], new: Dict[str, str]
                   ) -> Dict[str, Any]:
    """Name what changed between two signatures: the recompile-trigger
    attribution the ledger exists for."""
    changed = {k: {"from": old[k], "to": new[k]}
               for k in new if k in old and old[k] != new[k]}
    added = {k: new[k] for k in new if k not in old}
    removed = {k: old[k] for k in old if k not in new}
    out: Dict[str, Any] = {}
    if changed:
        out["changed"] = changed
    if added:
        out["added"] = added
    if removed:
        out["removed"] = removed
    return out


def _cost_analysis(compiled) -> Dict[str, Optional[float]]:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = ca.get("flops")
        by = ca.get("bytes accessed")
        return {"flops": float(flops) if flops is not None else None,
                "bytes_accessed": float(by) if by is not None else None}
    except Exception:
        return {"flops": None, "bytes_accessed": None}


def _memory_analysis(compiled) -> Dict[str, Dict[str, int]]:
    """The compiler's own count of the program's device memory (what it
    refuses a program by; the runtime's ``peak_bytes_in_use`` leaves
    temporaries out)."""
    try:
        m = compiled.memory_analysis()
        return {"memory": {
            "temp_bytes": int(m.temp_size_in_bytes),
            "argument_bytes": int(m.argument_size_in_bytes),
            "output_bytes": int(m.output_size_in_bytes),
            "alias_bytes": int(m.alias_size_in_bytes)}}
    except Exception:
        return {}


# ---------------------------------------------------------------------------
# the instrumented callable
# ---------------------------------------------------------------------------

class InstrumentedFn:
    """Wraps a jitted callable.  Ledger installed → every new signature
    compiles through the AOT path exactly once (recorded + cached + the
    compile shows on the trace timeline); ledger absent → pure
    pass-through.

    A call whose signature is already compiled does no per-leaf Python
    work.  The wrapper hands the arguments to the executable it expects
    them to fit, and that executable's own argument check
    (``jax.stages.Compiled``: pytree, avals, shardings — made before
    anything runs or any donated buffer is consumed) is what rejects a
    signature not yet seen; only then is the full per-leaf key built.
    Which executable to expect: the only one, or, where several
    signatures are compiled (prefill buckets), the one picked by the
    ``_leaf_key`` of the few leaves in which those signatures differ."""

    def __init__(self, fn, name: str):
        self._fn = fn
        self.name = name
        self._cache: Dict[Tuple, Any] = {}   # sig key -> compiled | None
        self._last_sig: Optional[Dict[str, str]] = None
        self._lock = threading.Lock()
        # (indices of the leaves whose keys tell the compiled signatures
        # apart, {those leaves' keys: (sig key, compiled)})
        self._expect: Tuple[Tuple[int, ...], Dict[Tuple, Tuple]] = ((), {})

    # builders/tests that lower the step themselves see through the seam
    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    def _cache_size(self) -> int:
        """Total compiled-program count behind this seam: the inner jit
        cache (ledger-off calls) plus this wrapper's AOT cache
        (ledger-on calls) — the compile-count pins keep working either
        way."""
        inner = getattr(self._fn, "_cache_size", None)
        n = int(inner()) if inner is not None else 0
        return n + sum(1 for v in self._cache.values() if v is not None)

    @property
    def wrapped(self):
        return self._fn

    def __call__(self, *args, **kwargs):
        ledger = _ACTIVE
        if ledger is None or kwargs:
            return self._fn(*args, **kwargs)
        import jax

        probe, table = self._expect
        if table:
            if probe:
                leaves = jax.tree_util.tree_leaves(args)
                try:
                    hit = table.get(tuple(_leaf_key(leaves[i])
                                          for i in probe))
                except IndexError:      # another tree: not seen yet
                    hit = None
            else:
                hit = next(iter(table.values()))
            if hit is not None:
                try:
                    return self._run(hit[0], hit[1], args)
                except (TypeError, ValueError):
                    # the executable's own argument check, made before
                    # it ran: these arguments are another signature
                    pass
        leaves, treedef = jax.tree_util.tree_flatten(args)
        # an outer jit/scan tracing through this wrapper must see the
        # raw function — AOT-compiling a tracer signature is meaningless
        if any(isinstance(l, jax.core.Tracer) for l in leaves):
            return self._fn(*args)
        key = (treedef, tuple(_leaf_key(l) for l in leaves))
        with self._lock:
            hit = key in self._cache
            compiled = self._cache.get(key)
        if not hit:
            compiled = self._compile_and_record(ledger, key, args)
        if compiled is not None:
            return self._run(key, compiled, args)
        return self._fn(*args)

    def _run(self, key, compiled, args):
        try:
            return compiled(*args)
        except (TypeError, ValueError):
            raise               # rejected before running: see __call__
        except Exception as e:
            # do NOT retry through the jit path: the failed dispatch
            # may already have consumed donated buffers (a retry
            # would die on "Array has been deleted"), and the
            # ORIGINAL error must propagate — a gloo/XLA peer-loss
            # error rewrapped by a retry would dodge the CLI's
            # is_peer_error -> exit 43 classification.  Later calls
            # for this signature use the jit path instead.
            with self._lock:
                self._cache[key] = None
                self._index()
            log(f"[compile_ledger] {self.name}: AOT executable "
                f"failed ({type(e).__name__}); later calls for this "
                "signature ride the jit path")
            raise

    def _index(self) -> None:
        """Rebuild ``_expect`` from the cache (under the lock, on a
        compile or a failure — never on a steady-state call)."""
        live = [(k, c) for k, c in self._cache.items() if c is not None]
        width = {len(k[1]) for k, _ in live}
        if len(width) != 1:     # nothing compiled, or trees of several
            self._expect = ((), {})     # sizes: every call builds its key
            return
        keys = [k[1] for k, _ in live]
        probe = tuple(i for i in range(width.pop())
                      if any(k[i] != keys[0][i] for k in keys))
        self._expect = (probe, {tuple(k[1][i] for i in probe): (k, c)
                                for k, c in live})

    def _compile_and_record(self, ledger: Ledger, key, args):
        from ..train import trace as trace_lib

        sig = _signature(args)
        rec: Dict[str, Any] = {
            "kind": "compile", "name": self.name,
            "t": round(time.time(), 6),
            "n_compile": len(self._cache) + 1,
            "signature": sig,
        }
        if self._last_sig is not None:
            rec.update(signature_diff(self._last_sig, sig))
        compiled = None
        lower = getattr(self._fn, "lower", None)
        if lower is not None:
            try:
                with trace_lib.span(f"compile:{self.name}"):
                    t0 = time.perf_counter()
                    with ledger.collecting_notes() as notes:
                        lowered = lower(*args)
                    t1 = time.perf_counter()
                    compiled = lowered.compile()
                    t2 = time.perf_counter()
                rec.update(notes)
                rec["lower_ms"] = round((t1 - t0) * 1e3, 3)
                rec["compile_ms"] = round((t2 - t1) * 1e3, 3)
                try:
                    rec["hlo_sha256"] = hashlib.sha256(
                        lowered.as_text().encode()).hexdigest()
                except Exception:
                    rec["hlo_sha256"] = None
                rec.update(_cost_analysis(compiled))
                rec.update(_memory_analysis(compiled))
            except Exception as e:  # lowering unsupported here: degrade
                compiled = None
                rec["note"] = f"aot-unavailable: {type(e).__name__}: {e}"
        else:
            rec["note"] = "no .lower (plain callable): signature-only"
        with self._lock:
            self._cache[key] = compiled
            self._last_sig = sig
            self._index()
        ledger.record(rec)
        return compiled


def instrument(fn, name: str):
    """Wrap ``fn`` under the ledger seam.  Idempotent-ish: wrapping an
    already-instrumented fn re-labels it instead of stacking."""
    if isinstance(fn, InstrumentedFn):
        fn.name = name
        return fn
    return InstrumentedFn(fn, name)
