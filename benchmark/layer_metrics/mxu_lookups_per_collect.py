"""Calls of the fused join-aggregate's one-program path per collect whose
dimension lookup rode the MXU one-hot contraction (counter
``join_lookups_mxu``: the build side's capacity is at most
``ops/mxugather.py`` ``MAX_TABLE_ROWS``); the others took the VPU gathers
(``join_lookups_vpu``).  None for a program that counts neither."""


def read(run):
    mxu = run.counters.get("join_lookups_mxu")
    if mxu is None:
        return None
    return mxu / run.window.collects
