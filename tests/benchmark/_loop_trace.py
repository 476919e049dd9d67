"""A synthetic trace shaped like an Anakin run's, and its damaged twins
(trace_reduce.py, "unreadable"): a session that opened 300 ps into a learner
execution and closed 300 ps into another, with `windows` evaluator executions
and `windows - 1` whole learner executions between. Times are picoseconds.

    learner tail 700 | eval 100 | learner 1000 | eval 100 | ... | learner head 300

One period (eval + learner) is 1100 ps, and tail + head are one learner, so
the window holds `windows` whole periods, as a run's does. An evaluator
execution is 90 ps of one fusion and 10 idle; a learner execution is rollout
600 (policy 100, env 500), gae 50, ppo_epoch 300 (shuffle 100, SGD 200) and
50 idle. `lost="first"` is the trace the profiler leaves when it loses the
boundary after the FIRST evaluator execution (PR 26's `d_change_t4`): that
evaluator's module event runs on to the end of the next learner execution,
whose ops carry no program, no path and no name but `region.<n>`.
`lost="last"` loses the boundary after the LAST one (`d_parent_t2`): the
module event swallows the learner head the session ended in. With
`keep_programs` the swallowed execution's ops keep their program, as a run of
PR 27 showed them: only the module line is wrong.
"""

import _paths  # noqa: F401
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.trace_reduce import Event

D0, D1 = "/device:TPU:0", "/device:TPU:1"
BASE = "jit(learner_fn)/while/body/closed_call"
TAIL, EVAL, LEARN, HEAD = 700, 100, 1000, 300
PERIOD = EVAL + LEARN

# (instruction, offset in the execution, duration, framework path)
LEARNER_OPS = [
    ("fusion.1", 0, 100, f"{BASE}/rollout/rollout_policy/dot_general"),
    ("fusion.2", 100, 500, f"{BASE}/rollout/rollout_env/mul"),
    ("fusion.3", 600, 50, f"{BASE}/vmap(gae)/while/body/add"),
    ("gather.5", 650, 100, f"{BASE}/ppo_epoch/minibatch_shuffle/gather"),
    ("fusion.6", 750, 200, f"{BASE}/ppo_epoch/ppo_minibatch/transpose(jvp(torso))/dot_general"),
]


def _learner(plane, start, first=0, last=LEARN, named=True):
    """The ops of the learner execution that began at `start`, cut to its
    [first, last) part; unnamed as the profiler leaves a lost execution's."""
    events = []
    for number, (name, offset, dur, path) in enumerate(LEARNER_OPS):
        begin, end = max(offset, first), min(offset + dur, last)
        if end <= begin:
            continue
        if named:
            stats = {"tf_op": path, "program": "jit_learner_fn"}
            events.append(Event(plane, tr.OPS_LINE, f"%{name} = f32[8]{{0}} thing()", start + begin, end - begin, stats))
        else:
            events.append(Event(plane, tr.OPS_LINE, f"region.{number}", start + begin, end - begin, {}))
    return events


def _evaluator(plane, start):
    stats = {"tf_op": "jit(_shard_eval)/while/body/tanh", "program": "jit__shard_eval"}
    return [Event(plane, tr.OPS_LINE, "%fusion.9 = f32[8]{0} thing()", start, EVAL - 10, stats)]


def _module(plane, name, start, end):
    return Event(plane, tr.MODULES_LINE, name, start, end - start, {})


def loop_events(windows=2, lost=None, planes=(D0,), damaged_planes=None, keep_programs=False):
    """The events of the trace; `lost` in (None, "first", "last") damages the
    planes in `damaged_planes` (all of `planes` if None)."""
    events = []
    for plane in planes:
        damage = lost if damaged_planes is None or plane in damaged_planes else None
        end = TAIL + windows * PERIOD - LEARN + HEAD
        events += _learner(plane, TAIL - LEARN, first=LEARN - TAIL)
        events.append(_module(plane, "jit_learner_fn(7)", 0, TAIL))
        for window in range(windows):
            eval_start = TAIL + window * PERIOD
            learn_start = eval_start + EVAL
            final = window == windows - 1
            swallowed = (damage == "first" and window == 0) or (damage == "last" and final)
            learn_end = end if final else learn_start + LEARN
            events += _evaluator(plane, eval_start)
            events += _learner(
                plane, learn_start, last=HEAD if final else LEARN, named=keep_programs or not swallowed
            )
            if swallowed:
                events.append(_module(plane, "jit__shard_eval(8)", eval_start, learn_end))
            else:
                events.append(_module(plane, "jit__shard_eval(8)", eval_start, learn_start))
                events.append(_module(plane, "jit_learner_fn(7)", learn_start, learn_end))
    return events


def loop_trace(windows=2, lost=None, planes=(D0,), damaged_planes=None, keep_programs=False):
    return tr.Trace.from_events(loop_events(windows, lost, planes, damaged_planes, keep_programs))
