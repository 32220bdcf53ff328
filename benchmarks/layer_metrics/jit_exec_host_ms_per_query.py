"""Host time of jit_exec in the traced slice, per query: self time of
``jit.pack`` (plans, flats, packed operands), ``jit.enqueue`` (the compiled
call) and ``jit.unpack`` (results from the fetched arrays). ``jit.drain``,
which waits for the device, is ``drain_wait_ms_per_dispatch``."""
from benchmarks.span_common import JIT_HOST, self_ms_per_query


def read(ctx):
    return self_ms_per_query(ctx, JIT_HOST)
