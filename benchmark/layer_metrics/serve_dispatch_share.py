"""Layer: serving/engine.py host loop. `dispatch` span time over the
drain's wall time: the uploads (block table, positions, tokens, active;
a chunk's ids) and the call of the compiled step, i.e. the host work
before the device can start a decode step or a prefill chunk. The call
also blocks while the runtime's queue of launches is full (about eight
chunks dispatched with no fetch between them, as when every slot
ingests at the start of a drain): that part is wait, the device busy.
"""

from benchmark.harness.iteration import span_share


def compute(record):
    return span_share(record, "dispatch")
