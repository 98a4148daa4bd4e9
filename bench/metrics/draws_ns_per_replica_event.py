"""Device time of the draw precompute: the ops under the program's
``event_loop.draws`` named scope (``precompute_draws`` in
``kernels/event_loop/ops.py`` and the padding of its streams), summed
over devices, per replica-event swept in the traced window. It reads an
op's name as ``devtrace.summarize`` gives it, which holds the scope only
where the trace gives the op's JAX path (``tf_op``). A program without
that scope, or a trace without op paths (a TPU v5e's ``XLA Ops`` events
hold only the HLO text and times), reads None, not 0."""
from bench import devtrace

#: the scope's name as it appears in an op's path (the trace's ``tf_op``)
PATTERN = r"event_loop\.draws"


def read(ctx):
    if not devtrace.op_count(ctx.summary, PATTERN):
        return None
    return devtrace.op_ns(ctx.summary, PATTERN) / ctx.replica_events
