"""What the six set-up metrics share: the training thread's time from the
set-up's start to the window's ``fit`` open, cut into disjoint parts.

At each instant the innermost program span of the in-memory record decides
(``program_spans.innermost``), and a ``compile:`` span wins over whatever
holds it. A span's part is that of its root, the outermost span it lies in:

- ``import``: the ``import`` rows (the package's import, JAX's among it,
  and ``train.py``'s module body);
- ``main``: ``train.py::main``'s five ``main_*`` spans;
- ``steps``: the ``fit`` calls that closed before the window's: the one of
  ``train.main`` (``--epochs 0``: ``init_state`` runs there), the three
  compared calls and the warm-up;
- ``before_main``: under no span, before the first ``main_args`` opened
  (under ``benchmark/run.py`` mostly the backend's start, ``jax.devices()``
  in ``harness.find_chips``);
- ``outside``: under no span after that: the driver's own work between the
  program's calls (rows, weights, the compared readings' norms,
  ``block_until_ready``), with any other root span's seconds;
- ``compile``: the ``compile:`` spans' own wall time.

The parts add up to the total exactly, in nanoseconds. The set-up's start
is ``benchmark/run.py``'s ``STARTED`` (the instant ``setup_s`` counts from,
on ``time.time()``), moved onto the record's ``perf_counter`` clock by the
offset read at reading time; a process without it (a test that calls
``run_cell``) starts at the first ``import`` row. A record without an
``import`` row (an older commit) gives None.
"""

import bisect
import sys
import time

import reduce as reducer
from layer_metrics import program_spans as ps

IMPORT = "import"
MAIN = ("main_args", "main_runtime", "main_data", "main_model", "main_trainer")
PARTS = ("import", "before_main", "main", "steps", "outside")


def start(imports):
    """(the set-up's start on the ``perf_counter_ns`` clock, whence)."""
    started = getattr(sys.modules.get("__main__"), "STARTED", None)
    if started is not None:
        offset = time.time_ns() - time.perf_counter_ns()
        return int(started * 1e9) - offset, "benchmark/run.py's STARTED"
    return min(r.start_ns for r in imports), "the first import row's start"


def part_of(root):
    if root is not None and root.name == IMPORT:
        return "import"
    if root is not None and root.name in MAIN:
        return "main"
    if root is not None and root.name == "fit":
        return "steps"
    return "outside"


def read_parts(run):
    """The parts, worked once a run and kept in it (None where there is
    nothing to read): ``ns`` (part -> nanoseconds, ``compile`` among them),
    ``total``, ``names`` (part -> {innermost span's name: ns}),
    ``compile_in`` (part of the compile spans' roots -> ns), ``gaps``
    ([(ns, the root span before)] after ``main_args`` opened),
    ``before_import`` (ns), ``imports``, ``mains`` and ``fits`` (the
    ``import``, ``main_*`` and set-up ``fit`` rows), ``fit`` (the
    window's), ``whence`` and ``args_s`` (the compile rows' trace + lower +
    compile seconds, all that opened before the window: what the two
    compile metrics sum)."""
    if "setup_parts" not in run:
        t0 = time.perf_counter()
        parts = run["setup_parts"] = _work(ps.record())
        if parts is not None:
            ns = parts["ns"]
            ps.say(run, "setup parts: %s + compile wall %.3f = %.3f s; total "
                   "%.3f s (the window fit's open less the set-up's start, "
                   "%s); the compile spans' trace + lower + compile args "
                   "%.3f s; parts read in %.1f ms" % (
                       " + ".join(f"{p} {ns[p] / 1e9:.3f}" for p in PARTS),
                       ns["compile"] / 1e9, sum(ns.values()) / 1e9,
                       parts["total"] / 1e9, parts["whence"], parts["args_s"],
                       (time.perf_counter() - t0) * 1e3,
                   ))
    return run["setup_parts"]


def _work(rows):
    fit, _ = ps.window_fit(rows)
    imports = [r for r in rows if r.name == IMPORT]
    if fit is None or not imports:
        return None
    lo, whence = start(imports)
    hi = fit.start_ns
    by_id = {r.id: r for r in rows}
    mine = [r for r in rows
            if r.thread == fit.thread and r.start_ns < hi and r.end_ns > lo]
    fits = sorted((r for r in mine if r.name == "fit"), key=lambda r: r.start_ns)
    labels = {r.id: f"fit {k}" for k, r in enumerate(fits, 1)}

    def root_of(r):
        root = by_id.get(r.root)
        return root, labels.get(r.root, root.name if root else r.name)

    compiles = [r for r in mine if r.name.startswith(ps.COMPILE)]
    compiled = reducer.union(reducer.clip(
        [(r.start_ns, r.end_ns) for r in compiles], lo, hi
    ))
    ns = {p: 0 for p in PARTS}
    ns["compile"] = reducer.measure(compiled)
    names = {p: {} for p in PARTS}
    covered, ends = [tuple(c) for c in compiled], []
    segments = ps.innermost([
        ((part_of(root_of(r)[0]), r.name, root_of(r)[1]), r.start_ns,
         r.end_ns - r.start_ns)
        for r in mine if not r.name.startswith(ps.COMPILE)
    ])
    for a, b, (part, name, root) in segments:
        kept = reducer.subtract(reducer.clip([(a, b)], lo, hi), compiled)
        n = reducer.measure(kept)
        ns[part] += n
        names[part][name] = names[part].get(name, 0) + n
        covered += kept
        ends.append((b, root))

    compile_in = {}
    for r in compiles:
        compile_in.setdefault(part_of(root_of(r)[0]), []).append(
            (r.start_ns, r.end_ns))
        ends.append((r.end_ns, root_of(r)[1]))
    compile_in = {
        p: reducer.measure(reducer.union(reducer.clip(spans, lo, hi)))
        for p, spans in compile_in.items()
    }

    mains = [r for r in mine if r.name in MAIN]
    main_at = min(
        (r.start_ns for r in mains if r.name == "main_args"), default=hi
    )
    gaps = reducer.subtract([(lo, hi)], reducer.union(covered))
    ns["before_main"] = reducer.measure(reducer.clip(gaps, lo, main_at))
    outside = reducer.clip(gaps, main_at, hi)
    ns["outside"] += reducer.measure(outside)
    ends.sort()
    times = [t for t, _ in ends]
    labelled = []
    for a, b in outside:
        i = bisect.bisect_right(times, a) - 1
        labelled.append((b - a, ends[i][1] if i >= 0 else "-"))
    first_import = min(r.start_ns for r in imports)
    return {
        "ns": ns, "total": hi - lo, "names": names, "compile_in": compile_in,
        "gaps": labelled,
        "before_import": reducer.measure(reducer.clip(gaps, lo, first_import)),
        "imports": imports, "mains": mains, "fits": fits, "fit": fit,
        "whence": whence,
        "args_s": sum(
            r.args["trace_s"] + r.args["lower_s"] + r.args["compile_s"]
            for r in rows
            if r.name.startswith(ps.COMPILE) and r.args and r.start_ns < hi
        ),
    }


def largest_first(ns_by_name):
    """``name s, ...`` from {name: ns}, largest first."""
    return ", ".join(
        f"{n} {v / 1e9:.3f}"
        for n, v in sorted(ns_by_name.items(), key=lambda kv: -kv[1]) if v > 0
    )
