"""The comparison that decides ``correct``.

Every answer the timed collects returned is compared, after the window
has closed, with the plain numpy reference of the same seed's data.  The
configurations promise exact decimal sums, every group, every collect
answered and no CPU fallback, so every limit is 0: an exact comparison."""
from __future__ import annotations

FALLBACK_COUNTERS = (
    "runtime_fallbacks", "query_fallbacks", "breaker_plan_fallbacks",
    "advisor_plan_fallbacks", "file_decoder_fallbacks",
    "chunk_decode_fallbacks")


def compare(answers, want: dict, *, failed: int, fallbacks: int,
            compiles_in_window: int) -> dict:
    """``answers``: one ``{group key: exact int}`` per completed collect,
    ``want``: the reference's.  Returns ``{name: {"value", "limit"}}``;
    the run is correct when no value passes its limit."""
    wrong = 0
    worst = 0
    missing = 0
    for got in answers:
        miss = len(want.keys() ^ got.keys())
        err = max((abs(got[k] - want[k]) for k in want.keys() & got.keys()),
                  default=0)
        missing = max(missing, miss)
        worst = max(worst, err)
        wrong += bool(miss or err)
    if not answers:
        # nothing to compare is no proof: count it as an unanswered collect
        failed = max(failed, 1)
    return {
        "wrong_answers": {"value": wrong, "limit": 0},
        "max_abs_err": {"value": worst, "limit": 0},
        "groups_off": {"value": missing, "limit": 0},
        "failed_collects": {"value": failed, "limit": 0},
        "fallbacks": {"value": fallbacks, "limit": 0},
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
    }


def is_correct(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


def lines(compared: dict):
    return [f"compared {name}: {c['value']} (limit {c['limit']})"
            for name, c in compared.items()]
