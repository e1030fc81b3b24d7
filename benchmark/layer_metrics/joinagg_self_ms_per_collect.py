"""Self time of the fused join-aggregate per collect, in ms: the self time
of ``srt.op.TpuJoinAggFusedExec`` and of every span beneath it that is not
inside another operator (``srt.join.build``, ``srt.joinagg.unique``,
``srt.joinagg.probe_sizes``, ``srt.joinagg.mat_agg``, their launches and
syncs), that is, of the paths whose last ``srt.op.*`` part is the fused
node; not the scans and the broadcast exchange it pulls.

The caveat of ``exchange_self_ms_per_collect`` holds here too: launches
are asynchronous, so this holds the device work the node WAITED for at
its sync (the overflow check of the aggregate's group ladder), which is
the whole fused program.

From the folded span table (``_spans.py``)."""
from benchmark.layer_metrics._spans import self_time


def _joinagg(path):
    ops = [p for p in path if p.startswith("srt.op.")]
    return bool(ops) and ops[-1] == "srt.op.TpuJoinAggFusedExec"


def read(run):
    return self_time(run, _joinagg, 1e6)
