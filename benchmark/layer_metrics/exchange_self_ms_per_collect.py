"""Self time of the shuffle exchanges per collect, in ms: the self time
of ``srt.op.TpuShuffleExchangeExec`` and of every span beneath it that
is not inside another operator (its launches, its syncs,
``srt.exchange.*``), that is, of the paths whose last ``srt.op.*``
part is the exchange.

Launches are asynchronous, so an operator's self time holds the device
work it WAITED for, which an earlier operator may have launched; only a
sync pins device time to an operator.  With a busy device and a few
syncs a collect, self time by operator is a split of the device time
among the operators that synced, not a per-kernel profile.

From the folded span table (``_spans.py``)."""
from benchmark.layer_metrics._spans import self_time


def _exchange(path):
    ops = [p for p in path if p.startswith("srt.op.")]
    return bool(ops) and ops[-1] == "srt.op.TpuShuffleExchangeExec"


def read(run):
    return self_time(run, _exchange, 1e6)
