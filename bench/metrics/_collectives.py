"""The collective ops of a chip's trace, on the ``XLA Ops`` line and on the
``Async XLA Ops`` line where an asynchronous collective runs from its start
to its done. An op is named as its HLO instruction: after the compiler's
opcode (``all-reduce.3``, ``all-gather-start.1``) or, for those JAX emits,
after JAX's primitive (``psum.2``, ``all_gather.7``, ``reduce_scatter.1``,
``ppermute.4``).

The profiler can drop a chip's events, so that its plane holds fewer of the
window's steps than another chip's; a time per step is read from the
planes that hold the most steps, over the steps they hold."""
import bisect

import xplane

KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute", "psum", "pmax", "pmin", "ppermute")
ASYNC_LINE = "Async XLA Ops"
# the program that runs once a step and holds the step's exchange
STEP_PROGRAM = "jit_dense_fwd_bwd"


def is_collective(name):
    return xplane.base(name).replace("_", "-").startswith(KINDS)


def intervals(plane, win):
    """The chip's collective time in the window, as a union of
    intervals."""
    evs = [e for ln in (xplane.OPS_LINE, ASYNC_LINE)
           for e in xplane.line(plane, ln) if is_collective(e[0])]
    return xplane.union(xplane.clip(evs, win))


def exposed(plane, win):
    """The parts of the collective time in which no other op (control flow
    aside, whose span holds its body's ops) runs on the chip's ``XLA Ops``
    line."""
    busy = xplane.union(xplane.clip(
        [e for e in xplane.leaf_ops(plane, win) if not is_collective(e[0])],
        win))
    out, j = [], 0
    for s, t in intervals(plane, win):
        while j < len(busy) and busy[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(busy) and busy[k][0] < t:
            if busy[k][0] > cur:
                out.append((cur, busy[k][0]))
            cur = max(cur, busy[k][1])
            k += 1
        if cur < t:
            out.append((cur, t))
    return out


def steps_held(plane, win):
    """How many runs of the step's program that start in the window the
    chip's plane holds ops of."""
    starts = sorted(e[1] for e in xplane.leaf_ops(plane, win))
    held = 0
    for name, s, d in xplane.line(plane, xplane.MODULES_LINE):
        if name.startswith(STEP_PROGRAM) and win[0] <= s < win[1]:
            i = bisect.bisect_left(starts, s)
            held += i < len(starts) and starts[i] < s + d
    return held


def ms_per_step(run, ns_of_plane):
    """``ns_of_plane`` per held step in ms, the mean over the planes that
    hold the most steps; None where no plane holds one."""
    held = [(steps_held(p, run.trace_window), p) for p in run.planes]
    most = max((n for n, _ in held), default=0)
    if most == 0:
        return None
    per = [ns_of_plane(p) / n for n, p in held if n == most]
    return sum(per) / len(per) * 1e-6
