"""Host time of the sweep scheduler per lane batch in the traced window.

Source: the program's spans (``repro.obs``), which the profiler records
on the ``/host:CPU`` plane of the trace: the self time of
``sweep.prepare``, ``sweep.pack``, ``lane.pad``, ``lane.dispatch``,
``lane.unpack`` and ``sweep.finish_rows`` over the number of
``lane.batch`` spans.  Self time is a span's duration less what its child
spans cover; of these six, only ``sweep.pack`` (child ``sweep.await``)
and ``sweep.prepare`` (child ``trace.build``) have program spans below
them.  The trace's host events carry no thread, so a child is taken off
once where a span of its parent's name contains it.
Waiting is left out: ``sweep.await`` and ``lane.fetch`` are not summed.
Moves ``accesses_per_s``: this host work stands between lane batches on
the device.  A program without these spans gives nothing.
"""

HOST_STAGES = ("sweep.prepare", "sweep.pack", "lane.pad", "lane.dispatch",
               "lane.unpack", "sweep.finish_rows")
#: the program span below each stage that has one
CHILD = {"sweep.pack": "sweep.await", "sweep.prepare": "trace.build"}
BATCH = "lane.batch"


def read(ctx):
    events = ctx.reduced.host_events
    batches = sum(1 for _, _, name in events if name == BATCH)
    stages = [(s, e, name) for s, e, name in events if name in HOST_STAGES]
    if batches == 0 or not stages:
        return None
    total = sum(e - s for s, e, _ in stages)
    for parent, child in CHILD.items():
        spans = [(s, e) for s, e, name in stages if name == parent]
        for cs, ce, name in events:
            if name != child:
                continue
            if any(s <= cs and ce <= e for s, e in spans):
                total -= ce - cs
    return total / 1e6 / batches
