"""Time runnable work waited for the interpreter, per query. The program
takes a thread's CPU (``thread_time_ns``) in the outermost span the thread
has open; the *work* of that stretch is the self time of its spans that do
not block by design (all but ``rest.read``, ``rest.write``, ``jit.drain``,
``scheduler.queue``, ``action.msearch``). Work less CPU, summed over the
stretches of the traced slice: time in which a thread was runnable and did
not run — it waited for the interpreter lock, or for a core."""
from benchmarks.span_common import analysis, queries_in_slice


def read(ctx):
    an = analysis(ctx)
    n = queries_in_slice(ctx) if an else 0.0
    if an is None or n <= 0 or not an["threads"]:
        return None
    return sum(work - cpu for _c, work, cpu
               in an["threads"].values()) / 1e6 / n
