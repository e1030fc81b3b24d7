"""``exec/join.py`` ``_merge_lookup``: match and build position of every
probe key from the one merge sort, against a plain reference (a sorted
list, ``bisect`` and a compare) and against what it replaces in the
fused star join, ``_merge_rank(..., "left")`` + ``words[loc] == query``.

One compiled program a key width: every case has the same capacities,
and ``n_valid`` is an operand."""
import bisect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.exec.join import _merge_lookup, _merge_rank

CAP_B, CAP_Q = 64, 128
I64 = np.iinfo(np.int64)


@jax.jit
def _lookup_and_what_it_replaces(bwords, n_valid, qwords):
    loc, matched = _merge_lookup(list(bwords), n_valid, list(qwords))
    lo = _merge_rank(list(bwords), n_valid, list(qwords), "left")
    at = jnp.clip(lo, 0, CAP_B - 1)
    eq = lo < n_valid
    for w, q in zip(bwords, qwords):
        eq = eq & (w[at] == q)
    return loc, matched, lo, eq


def _keys(rng, n, n_words, lo=-50, hi=50):
    """``n`` distinct keys of ``n_words`` words, ascending."""
    seen = set()
    while len(seen) < n:
        seen.add(tuple(int(x) for x in rng.integers(lo, hi, n_words)))
    return sorted(seen)


def _case(name, n_words, seed=30):
    """(valid build keys, tail keys, probe keys) of one case."""
    rng = np.random.default_rng(seed)
    pool = _keys(rng, 80, n_words)
    valid, tail, probes = pool[:40], pool[40:52], None
    if name == "mixed":
        # below, between, above, equal, repeated; the valid keys spread
        # over the pool so that some probes fall between two of them
        valid, tail = pool[5:75:2], pool[6:30:2]
        probes = pool[:5] + pool[75:] + pool[5:75] + pool[10:20] * 2
    elif name == "none_valid":
        valid, tail = [], pool[:52]
        probes = pool[:60]
    elif name == "all_valid":
        valid, tail = pool[:CAP_B], []
        probes = pool
    elif name == "tail_only_matches":
        # every probe equals a filtered (or null-keyed) build row's key
        probes = tail * 4
    elif name == "one_key_repeated":
        probes = [valid[17]] * CAP_Q
    elif name == "below_and_above":
        lo_key = tuple([-60] * n_words)
        hi_key = tuple([60] * n_words)
        probes = [lo_key, hi_key] * 20
    elif name == "extremes":
        ext = [tuple([I64.min] * n_words), tuple([I64.max] * n_words),
               tuple([0] * n_words)]
        valid = sorted(valid[:30] + ext)
        probes = ext * 3 + valid[:10] + [(I64.min + 1,) * n_words,
                                         (I64.max - 1,) * n_words]
    assert probes is not None, name
    assert len(valid) + len(tail) <= CAP_B and len(probes) <= CAP_Q
    return valid, tail, probes


CASES = ["mixed", "none_valid", "all_valid", "tail_only_matches",
         "one_key_repeated", "below_and_above", "extremes"]


def _words(keys, cap, n_words):
    """Key tuples -> ``n_words`` int64 arrays of ``cap`` rows; the rows
    beyond the keys are padding (key 0)."""
    out = np.zeros((n_words, cap), np.int64)
    for i, k in enumerate(keys):
        out[:, i] = k
    return tuple(out)


@pytest.mark.parametrize("n_words", [1, 2], ids=["one_word", "two_words"])
@pytest.mark.parametrize("name", CASES)
def test_merge_lookup_matches_the_reference(name, n_words):
    valid, tail, probes = _case(name, n_words)
    # the build side as _build_fn leaves it: valid keys ascending, then
    # the filtered and null-keyed rows ascending, then padding
    bwords = _words(valid + sorted(tail), CAP_B, n_words)
    qwords = _words(probes, CAP_Q, n_words)
    loc, matched, lo, eq = (
        np.asarray(x) for x in _lookup_and_what_it_replaces(
            bwords, np.int32(len(valid)), qwords))

    # the plain reference: searchsorted over the valid keys and a compare
    want_at = [bisect.bisect_left(valid, p) for p in probes]
    want = np.array([a < len(valid) and valid[a] == p
                     for a, p in zip(want_at, probes)], bool)
    n = len(probes)
    assert matched[:n].tolist() == want.tolist()
    assert loc[:n][want].tolist() == [a for a, w in zip(want_at, want) if w]
    assert ((0 <= loc) & (loc < CAP_B)).all()
    # the rows beyond the probes: padding (and null keys, which the
    # caller masks with the key's validity) carry key 0 and match only
    # where a VALID build key is 0, never a tail or padding row of the
    # build side
    zero = tuple([0] * n_words)
    assert (matched[n:] == (zero in valid)).all()

    # and what it replaces, on every row, padding included
    assert matched.tolist() == eq.tolist()
    assert loc[matched].tolist() == lo[matched].tolist()
    assert matched[:n].any() == (name not in (
        "none_valid", "tail_only_matches", "below_and_above"))
