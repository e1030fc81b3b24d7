"""String expressions over the padded char-matrix layout.

Reference analog: org/apache/spark/sql/rapids/stringFunctions.scala
(GpuSubstring, GpuConcat, GpuUpper/GpuLower, GpuStringTrim, GpuContains,
GpuStartsWith/GpuEndsWith, GpuLength, GpuStringRepeat...).  cuDF implements
these over (chars, offsets); here every op is a dense (rows x width) vector
transform — gathers along the width axis with index arithmetic, which XLA
maps onto the VPU.

Unicode note: Upper/Lower are ASCII-only for now (the reference similarly
documents incompatibilities and hides some behind conf); Length counts UTF-8
*code points* like Spark, computed from the byte patterns.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.expr.base import (
    BinaryExpression,
    EvalContext,
    Expression,
    UnaryExpression,
)
from spark_rapids_tpu.expr.predicates import _pad_to


class Length(UnaryExpression):
    """UTF-8 code-point count (Spark length), not byte count."""

    def _resolve_type(self):
        self._dataType = T.INT
        self._nullable = self.child.nullable

    def do_columnar_eval(self, ctx, cols):
        c = cols[0]
        pos = jnp.arange(c.width)[None, :]
        in_str = pos < c.lengths[:, None]
        # count bytes that are NOT utf-8 continuation bytes (0b10xxxxxx)
        is_cont = (c.chars & 0xC0) == 0x80
        n = jnp.sum(in_str & ~is_cont, axis=1)
        return DeviceColumn(T.INT, c.validity, data=n.astype(jnp.int32))


class Upper(UnaryExpression):
    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = self.child.nullable

    def _tx(self, ch):
        return jnp.where((ch >= ord("a")) & (ch <= ord("z")), ch - 32, ch)

    def do_columnar_eval(self, ctx, cols):
        c = cols[0]
        return DeviceColumn(T.STRING, c.validity,
                            chars=self._tx(c.chars).astype(jnp.uint8),
                            lengths=c.lengths)


class Lower(Upper):
    def _tx(self, ch):
        return jnp.where((ch >= ord("A")) & (ch <= ord("Z")), ch + 32, ch)


class Substring(Expression):
    """substring(str, pos, len) with Spark 1-based / negative pos semantics.

    Byte-based gather; Spark substring is character-based — for ASCII they
    agree.  Non-ASCII correctness comes with the codepoint-index map
    (later round; tagged incompat until then, like the reference's CSV/regex
    caveats)."""

    def __init__(self, s: Expression, pos: Expression, length: Expression):
        super().__init__([s, pos, length])

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = True

    def do_columnar_eval(self, ctx: EvalContext, cols):
        c, p, ln = cols
        n = c.lengths
        pos = p.data.astype(jnp.int32)
        # Spark substringSQL: pos>0 -> 1-based; pos<0 -> from end (may land
        # before the start — the window is [start, start+len) computed on the
        # UNclamped start, then clipped, so a negative start eats length)
        start0 = jnp.where(pos > 0, pos - 1,
                           jnp.where(pos < 0, n + pos, 0))
        want = jnp.maximum(ln.data.astype(jnp.int32), 0)
        end0 = start0 + want
        start = jnp.clip(start0, 0, n)
        out_len = jnp.maximum(jnp.clip(end0, 0, n) - start, 0)
        width = c.width
        idx = start[:, None] + jnp.arange(width)[None, :]
        take = jnp.arange(width)[None, :] < out_len[:, None]
        gathered = jnp.take_along_axis(c.chars, jnp.clip(idx, 0, width - 1),
                                       axis=1)
        chars = jnp.where(take, gathered, 0).astype(jnp.uint8)
        validity = c.validity & p.validity & ln.validity
        return DeviceColumn(T.STRING, validity, chars=chars,
                            lengths=out_len.astype(jnp.int32))


class Concat(Expression):
    """concat(s1, s2, ...): null if any input null (Spark)."""

    def __init__(self, children: List[Expression]):
        super().__init__(children)

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = any(c.nullable for c in self.children)

    def do_columnar_eval(self, ctx, cols):
        total_w = sum(c.width for c in cols)
        n = cols[0].capacity
        out = jnp.zeros((n, total_w), jnp.uint8)
        out_len = jnp.zeros(n, jnp.int32)
        validity = cols[0].validity
        for c in cols[1:]:
            validity = validity & c.validity
        for c in cols:
            # scatter c's chars at position out_len per row
            idx = out_len[:, None] + jnp.arange(c.width)[None, :]
            take = jnp.arange(c.width)[None, :] < c.lengths[:, None]
            # build one-hot-ish scatter via take_along_axis on the source side:
            # for each output col j, find source col j - out_len
            src_idx = jnp.arange(total_w)[None, :] - out_len[:, None]
            in_range = (src_idx >= 0) & (src_idx < c.width)
            src = jnp.take_along_axis(
                _pad_to(c.chars, total_w),
                jnp.clip(src_idx, 0, total_w - 1), axis=1)
            write = in_range & (src_idx < c.lengths[:, None])
            out = jnp.where(write, src, out)
            out_len = out_len + c.lengths
            del idx, take
        return DeviceColumn(T.STRING, validity, chars=out, lengths=out_len)


class _FixedCompare(BinaryExpression):
    """contains/startswith/endswith with arbitrary (usually literal) needle."""

    def _resolve_type(self):
        self._dataType = T.BOOLEAN
        self._nullable = True


class StartsWith(_FixedCompare):
    def do_columnar_eval(self, ctx, cols):
        s, pre = cols
        w = max(s.width, pre.width)
        a = _pad_to(s.chars, w)
        b = _pad_to(pre.chars, w)
        pos = jnp.arange(w)[None, :]
        relevant = pos < pre.lengths[:, None]
        eq = jnp.all(~relevant | (a == b), axis=1)
        data = eq & (s.lengths >= pre.lengths)
        return DeviceColumn(T.BOOLEAN, s.validity & pre.validity, data=data)


class EndsWith(_FixedCompare):
    def do_columnar_eval(self, ctx, cols):
        s, suf = cols
        w = s.width
        start = s.lengths - suf.lengths
        idx = start[:, None] + jnp.arange(max(suf.width, 1))[None, :]
        gathered = jnp.take_along_axis(
            s.chars, jnp.clip(idx, 0, max(w - 1, 0)), axis=1)
        pos = jnp.arange(max(suf.width, 1))[None, :]
        relevant = pos < suf.lengths[:, None]
        b = suf.chars if suf.width else jnp.zeros_like(gathered)
        eq = jnp.all(~relevant | (gathered == _pad_to(b, gathered.shape[1])),
                     axis=1)
        data = eq & (s.lengths >= suf.lengths)
        return DeviceColumn(T.BOOLEAN, s.validity & suf.validity, data=data)


class Contains(_FixedCompare):
    def do_columnar_eval(self, ctx, cols):
        s, needle = cols
        # shared first-match scan (also backs instr/locate)
        matches = _first_match_pos(s, needle) > 0
        return DeviceColumn(T.BOOLEAN, s.validity & needle.validity,
                            data=matches)


class StringTrim(UnaryExpression):
    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = self.child.nullable

    def do_columnar_eval(self, ctx, cols):
        c = cols[0]
        pos = jnp.arange(c.width)[None, :]
        in_str = pos < c.lengths[:, None]
        is_ws = (c.chars == ord(" ")) & in_str
        nonws = in_str & ~is_ws
        any_nonws = jnp.any(nonws, axis=1)
        first = jnp.where(any_nonws, jnp.argmax(nonws, axis=1), 0)
        last = jnp.where(any_nonws,
                         c.width - 1 - jnp.argmax(nonws[:, ::-1], axis=1), -1)
        out_len = (last - first + 1).astype(jnp.int32)
        idx = first[:, None] + jnp.arange(c.width)[None, :]
        take = jnp.arange(c.width)[None, :] < out_len[:, None]
        gathered = jnp.take_along_axis(c.chars, jnp.clip(idx, 0, c.width - 1),
                                       axis=1)
        chars = jnp.where(take, gathered, 0).astype(jnp.uint8)
        return DeviceColumn(T.STRING, c.validity, chars=chars, lengths=out_len)


class Like(BinaryExpression):
    """SQL LIKE with literal pattern, compiled at plan time to device ops.

    Reference analog: GpuLike; complex patterns fall back at tag time (the
    regex-transpiler-reject path, SURVEY.md §2.5).  Supported here:
    'abc%', '%abc', '%abc%', exact, and patterns without wildcards; others
    are rejected by the overrides layer (try_compile_like)."""

    def _resolve_type(self):
        self._dataType = T.BOOLEAN
        self._nullable = True

    def do_columnar_eval(self, ctx, cols):
        from spark_rapids_tpu.expr.base import Literal

        s, _ = cols
        pat = self.right
        assert isinstance(pat, Literal), "LIKE pattern must be literal"
        p: str = pat.value
        simple = "_" not in p and "\\" not in p
        core = p.strip("%")
        if simple and "%" not in core:
            needle = Literal(core, T.STRING).eval_tpu(ctx)
            if p.startswith("%") and p.endswith("%"):
                return Contains(self.left, pat).do_columnar_eval(
                    ctx, [s, needle])
            if p.endswith("%"):
                return StartsWith(self.left, pat).do_columnar_eval(
                    ctx, [s, needle])
            if p.startswith("%"):
                return EndsWith(self.left, pat).do_columnar_eval(
                    ctx, [s, needle])
            from spark_rapids_tpu.expr.predicates import string_compare

            _, eq = string_compare(s, needle)
            return DeviceColumn(T.BOOLEAN, s.validity, data=eq)
        # general patterns (underscores, inner %, escapes): full-match DFA
        from spark_rapids_tpu.regex import compile_regex, like_to_regex

        compiled = getattr(self, "_dfa", None)
        if compiled is None:
            compiled = self._dfa = compile_regex(like_to_regex(p),
                                                 full_match=True)
        return DeviceColumn(T.BOOLEAN, s.validity, data=run_dfa(s, compiled))


def try_compile_like(p):
    """-> (supported, compiled-or-None).  Fast paths (prefix/suffix/
    contains/exact) need no DFA; everything else (underscores, inner %,
    escapes) compiles to a full-match DFA, returned so the tag-time caller
    can stash it on the expression (avoids a second compile at eval)."""
    if p is None:
        return False, None
    if "_" not in p and "\\" not in p:
        core = p.strip("%")
        if "%" not in core:
            return True, None
    from spark_rapids_tpu.regex import (
        RegexUnsupported,
        compile_regex,
        like_to_regex,
    )

    try:
        return True, compile_regex(like_to_regex(p), full_match=True)
    except (RegexUnsupported, ValueError):
        # invalid escape sequences error identically on the CPU path, so
        # letting them fall back surfaces the same Spark-style error there
        return False, None


# ---------------------------------------------------------------------------
# Breadth set: replace/translate/instr/locate/pad/repeat/reverse/initcap/
# ascii/chr/concat_ws.  Reference analog: stringFunctions.scala
# (GpuStringReplace, GpuStringTranslate, GpuStringInstr, GpuStringLocate,
# GpuStringLPad/RPad, GpuStringRepeat, GpuReverse, GpuInitCap, GpuAscii,
# GpuChr, GpuConcatWs).  All are dense (rows x width) vector transforms;
# where the reference requires literal needles/pads at plan time, the
# overrides layer enforces the same restriction here.
# ---------------------------------------------------------------------------


def _literal_bytes(e: Expression) -> bytes:
    from spark_rapids_tpu.expr.base import Literal

    assert isinstance(e, Literal) and e.value is not None
    return e.value.encode("utf-8")


def _match_literal_at(c: DeviceColumn, needle: bytes) -> "jnp.ndarray":
    """(n, w) bool: needle matches starting at byte position i."""
    w = c.width
    ls = len(needle)
    m = jnp.ones((c.capacity, max(w, 1)), jnp.bool_)
    for k, b in enumerate(needle):
        if k >= w:
            m = jnp.zeros_like(m)
            break
        shifted = jnp.concatenate(
            [c.chars[:, k:], jnp.zeros((c.capacity, k), jnp.uint8)], axis=1)
        m = m & (shifted == b)
    pos = jnp.arange(max(w, 1))[None, :]
    return m & (pos + ls <= c.lengths[:, None])


class Reverse(UnaryExpression):
    """Byte-reverse (ASCII-only, like Upper/Lower)."""

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = self.child.nullable

    def do_columnar_eval(self, ctx, cols):
        c = cols[0]
        w = max(c.width, 1)
        idx = c.lengths[:, None] - 1 - jnp.arange(w)[None, :]
        take = jnp.arange(w)[None, :] < c.lengths[:, None]
        src = c.chars if c.width else jnp.zeros((c.capacity, 1), jnp.uint8)
        g = jnp.take_along_axis(src, jnp.clip(idx, 0, w - 1), axis=1)
        return DeviceColumn(T.STRING, c.validity,
                            chars=jnp.where(take, g, 0).astype(jnp.uint8),
                            lengths=c.lengths)


class InitCap(UnaryExpression):
    """First letter of each space-separated word upper, rest lower
    (ASCII-only)."""

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = self.child.nullable

    def do_columnar_eval(self, ctx, cols):
        c = cols[0]
        ch = c.chars
        is_space = ch == ord(" ")
        prev_space = jnp.concatenate(
            [jnp.ones((c.capacity, 1), jnp.bool_), is_space[:, :-1]], axis=1)
        lower = jnp.where((ch >= ord("A")) & (ch <= ord("Z")), ch + 32, ch)
        upper = jnp.where((ch >= ord("a")) & (ch <= ord("z")), ch - 32, ch)
        out = jnp.where(prev_space, upper, lower)
        return DeviceColumn(T.STRING, c.validity,
                            chars=out.astype(jnp.uint8), lengths=c.lengths)


class Ascii(UnaryExpression):
    """ascii(s): code of the first byte; 0 for empty (ASCII-only)."""

    def _resolve_type(self):
        self._dataType = T.INT
        self._nullable = self.child.nullable

    def do_columnar_eval(self, ctx, cols):
        c = cols[0]
        if not c.width:
            return DeviceColumn(T.INT, c.validity,
                                data=jnp.zeros(c.capacity, jnp.int32))
        # decode the first UTF-8 code point (Spark: codePointAt(0))
        b = [c.chars[:, k].astype(jnp.int32) if k < c.width
             else jnp.zeros(c.capacity, jnp.int32) for k in range(4)]
        one = b[0] < 0x80
        two = (b[0] >= 0xC0) & (b[0] < 0xE0)
        three = (b[0] >= 0xE0) & (b[0] < 0xF0)
        cp = jnp.where(
            one, b[0],
            jnp.where(two, ((b[0] & 0x1F) << 6) | (b[1] & 0x3F),
                      jnp.where(three,
                                ((b[0] & 0x0F) << 12) | ((b[1] & 0x3F) << 6)
                                | (b[2] & 0x3F),
                                ((b[0] & 0x07) << 18) | ((b[1] & 0x3F) << 12)
                                | ((b[2] & 0x3F) << 6) | (b[3] & 0x3F))))
        out = jnp.where(c.lengths > 0, cp, 0)
        return DeviceColumn(T.INT, c.validity, data=out)


class Chr(UnaryExpression):
    """chr(n): character with code n % 256 (UTF-8 encoded); n<0 -> ''."""

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = self.child.nullable

    def do_columnar_eval(self, ctx, cols):
        c = cols[0]
        lv = c.data.astype(jnp.int64)
        code = (lv % 256).astype(jnp.int32)  # python-style mod: >= 0
        neg = lv < 0
        two_byte = code >= 128
        b0 = jnp.where(two_byte, 0xC0 | (code >> 6), code)
        b1 = jnp.where(two_byte, 0x80 | (code & 0x3F), 0)
        chars = jnp.stack([b0, b1], axis=1).astype(jnp.uint8)
        out_len = jnp.where(neg, 0, jnp.where(two_byte, 2, 1)).astype(jnp.int32)
        chars = jnp.where(jnp.arange(2)[None, :] < out_len[:, None], chars, 0)
        return DeviceColumn(T.STRING, c.validity,
                            chars=chars.astype(jnp.uint8), lengths=out_len)


class StringReplace(Expression):
    """replace(str, search, rep) with literal search/rep: non-overlapping
    left-to-right, like Java String.replace.  Empty search returns str."""

    def __init__(self, s: Expression, search: Expression, rep: Expression):
        super().__init__([s, search, rep])

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = True

    def do_columnar_eval(self, ctx, cols):
        import jax

        c = cols[0]
        validity = self.and_validity(cols)
        search = _literal_bytes(self.children[1])
        rep = _literal_bytes(self.children[2])
        ls, lr = len(search), len(rep)
        if ls == 0 or c.width == 0 or ls > c.width:
            return DeviceColumn(T.STRING, validity, chars=c.chars,
                                lengths=c.lengths)
        n, w = c.capacity, c.width
        m = _match_literal_at(c, search)

        # greedy non-overlap: scan across columns with a per-row skip count
        def step(skip, m_col):
            start = m_col & (skip == 0)
            new_skip = jnp.where(start, ls - 1, jnp.maximum(skip - 1, 0))
            return new_skip, (start, skip > 0)

        _, (starts_t, covered_t) = jax.lax.scan(
            step, jnp.zeros(n, jnp.int32), m.T)
        starts, covered = starts_t.T, covered_t.T
        in_str = jnp.arange(w)[None, :] < c.lengths[:, None]
        contrib = jnp.where(in_str,
                            jnp.where(starts, lr,
                                      jnp.where(covered, 0, 1)), 0)
        off = jnp.cumsum(contrib, axis=1) - contrib  # exclusive
        n_rep_max = w // ls
        out_w = w + n_rep_max * max(lr - ls, 0)
        out_len = jnp.sum(contrib, axis=1).astype(jnp.int32)
        flat = jnp.zeros(n * out_w, jnp.uint8)
        rows = jnp.arange(n)[:, None]
        # plain chars
        tgt = jnp.where(in_str & ~starts & ~covered,
                        rows * out_w + off, n * out_w)
        flat = flat.at[tgt.reshape(-1)].set(c.chars.reshape(-1), mode="drop")
        # replacement bytes
        for k, b in enumerate(rep):
            tgt = jnp.where(in_str & starts, rows * out_w + off + k, n * out_w)
            flat = flat.at[tgt.reshape(-1)].set(
                jnp.uint8(b), mode="drop")
        return DeviceColumn(T.STRING, validity,
                            chars=flat.reshape(n, out_w), lengths=out_len)


class StringTranslate(Expression):
    """translate(str, from, to) with literal from/to; unmatched from-chars
    are deleted (ASCII-only byte mapping)."""

    def __init__(self, s: Expression, frm: Expression, to: Expression):
        super().__init__([s, frm, to])

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = True

    def do_columnar_eval(self, ctx, cols):
        import numpy as np

        c = cols[0]
        validity = self.and_validity(cols)
        frm = _literal_bytes(self.children[1])
        to = _literal_bytes(self.children[2])
        table = np.arange(256, dtype=np.uint8)
        deleted = np.zeros(256, np.bool_)
        seen = set()
        for i, b in enumerate(frm):
            if b in seen:  # first occurrence wins (Java Spark behavior)
                continue
            seen.add(b)
            if i < len(to):
                table[b] = to[i]
            else:
                deleted[b] = True
        if c.width == 0:
            return DeviceColumn(T.STRING, validity, chars=c.chars,
                                lengths=c.lengths)
        mapped = jnp.take(jnp.asarray(table), c.chars.astype(jnp.int32))
        in_str = jnp.arange(c.width)[None, :] < c.lengths[:, None]
        drop = jnp.take(jnp.asarray(deleted), c.chars.astype(jnp.int32))
        keep = in_str & ~drop
        # stable compaction: sort by (dropped-or-padding) ascending
        perm = jnp.argsort(~keep, axis=1, stable=True)
        g = jnp.take_along_axis(mapped, perm, axis=1)
        out_len = jnp.sum(keep, axis=1).astype(jnp.int32)
        mask = jnp.arange(c.width)[None, :] < out_len[:, None]
        return DeviceColumn(T.STRING, validity,
                            chars=jnp.where(mask, g, 0).astype(jnp.uint8),
                            lengths=out_len)


def _first_match_pos(s: DeviceColumn, needle: DeviceColumn,
                     from_idx=None) -> "jnp.ndarray":
    """1-based CHARACTER position of the first needle occurrence at/after
    char index from_idx (0-based), 0 if absent.  Spark's instr/locate count
    code points (UTF8String.indexOf), not bytes: matching is byte-wise over
    the UTF-8 matrix, but reported positions count non-continuation bytes.
    Empty needle -> 1 regardless of start.

    Start positions are scanned in CHUNKS inside a lax.fori_loop — compile
    size is O(1) in the string width (a Python loop over `range(width)`
    unrolled a 2048-step program at the widest bucket: minutes of XLA
    compile), while each iteration stays a wide
    vectorized gather+compare so the MXU-adjacent VPU lanes stay busy.
    Peak scratch is capped at ~256MB via the chunk size."""
    w = max(s.width, 1)
    nw = max(needle.width, 1)
    cap = s.capacity
    npos = jnp.arange(nw)[None, :]
    relevant = npos < needle.lengths[:, None]
    nchars = (needle.chars if needle.width
              else jnp.zeros((cap, nw), jnp.uint8))
    schars = s.chars if s.width else jnp.zeros((cap, w), jnp.uint8)
    # chars_before[:, j] = number of code points strictly before byte j
    noncont = ((schars < 0x80) | (schars >= 0xC0)).astype(jnp.int32)
    chars_before = jnp.cumsum(noncont, axis=1) - noncont

    chunk = max(1, min(w, (1 << 28) // max(cap * nw, 1)))
    n_chunks = -(-w // chunk)

    def one_chunk(ci, carry):
        found, first = carry
        starts = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)  # (k,)
        idx = jnp.clip(starts[:, None] + jnp.arange(nw)[None, :],
                       0, w - 1)                                   # (k, nw)
        seg = jnp.take(schars, idx.reshape(-1), axis=1).reshape(
            cap, chunk, nw)
        eq = jnp.all(~relevant[:, None, :] | (seg == nchars[:, None, :]),
                     axis=2)                                       # (cap, k)
        in_range = starts[None, :] < w
        hit = (eq & in_range
               & (starts[None, :] + needle.lengths[:, None]
                  <= s.lengths[:, None]))
        cpos = jnp.take(chars_before, jnp.clip(starts, 0, w - 1), axis=1)
        if from_idx is not None:
            fi = from_idx if jnp.ndim(from_idx) == 0 else from_idx[:, None]
            hit = hit & (cpos >= fi)
        has = jnp.any(hit, axis=1)
        j = jnp.argmax(hit, axis=1)                 # first True (ascending)
        cand = jnp.take_along_axis(cpos, j[:, None], axis=1)[:, 0] + 1
        first = jnp.where(has & ~found, cand, first)
        return found | has, first

    found0 = jnp.zeros(cap, jnp.bool_)
    first0 = jnp.zeros(cap, jnp.int32)
    if n_chunks == 1:
        _, first = one_chunk(jnp.int32(0), (found0, first0))
    else:
        _, first = jax.lax.fori_loop(0, n_chunks, one_chunk,
                                     (found0, first0))
    return jnp.where(needle.lengths == 0, 1, first)


class StringInstr(BinaryExpression):
    """instr(str, substr): 1-based first occurrence; 0 if absent."""

    def _resolve_type(self):
        self._dataType = T.INT
        self._nullable = True

    def do_columnar_eval(self, ctx, cols):
        s, needle = cols
        return DeviceColumn(T.INT, s.validity & needle.validity,
                            data=_first_match_pos(s, needle))


class StringLocate(Expression):
    """locate(substr, str, start).  Spark semantics: start < 1 -> 0;
    null start -> 0 (valid); empty substr -> 1."""

    def __init__(self, substr: Expression, s: Expression,
                 start: Expression):
        super().__init__([substr, s, start])

    def _resolve_type(self):
        self._dataType = T.INT
        self._nullable = True

    def do_columnar_eval(self, ctx, cols):
        needle, s, st = cols
        start_val = st.data.astype(jnp.int32)
        first = _first_match_pos(s, needle, jnp.maximum(start_val - 1, 0))
        out = jnp.where(st.validity & (start_val >= 1), first, 0)
        return DeviceColumn(T.INT, s.validity & needle.validity, data=out)


class _PadBase(Expression):
    def __init__(self, s: Expression, ln: Expression, pad: Expression):
        super().__init__([s, ln, pad])

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = True

    def _parts(self, cols):
        from spark_rapids_tpu.expr.base import Literal

        c = cols[0]
        assert isinstance(self.children[1], Literal)
        target = max(int(self.children[1].value), 0)
        pad = _literal_bytes(self.children[2])
        return c, target, pad


class StringLPad(_PadBase):
    def do_columnar_eval(self, ctx, cols):
        import numpy as np

        c, target, pad = self._parts(cols)
        validity = self.and_validity(cols)
        if target == 0:
            return DeviceColumn(T.STRING, validity,
                                chars=jnp.zeros((c.capacity, 1), jnp.uint8),
                                lengths=jnp.zeros(c.capacity, jnp.int32))
        w = max(target, 1)
        spaces = jnp.maximum(target - c.lengths, 0)
        pad_np = np.frombuffer(pad, np.uint8)
        pad_cols = jnp.asarray(
            np.resize(pad_np, w) if len(pad) else np.zeros(w, np.uint8))
        j = jnp.arange(w)[None, :]
        src_idx = j - spaces[:, None]
        gw = max(c.width, w)
        src_chars = (_pad_to(c.chars, gw) if c.width
                     else jnp.zeros((c.capacity, gw), jnp.uint8))
        src = jnp.take_along_axis(src_chars,
                                  jnp.clip(src_idx, 0, gw - 1), axis=1)
        out = jnp.where(src_idx < 0, pad_cols[None, :], src)
        out_len = jnp.full(c.capacity, target, jnp.int32)  # always `target`
        mask = j < out_len[:, None]
        return DeviceColumn(T.STRING, validity,
                            chars=jnp.where(mask, out, 0).astype(jnp.uint8),
                            lengths=out_len)


class StringRPad(_PadBase):
    def do_columnar_eval(self, ctx, cols):
        import numpy as np

        c, target, pad = self._parts(cols)
        validity = self.and_validity(cols)
        if target == 0:
            return DeviceColumn(T.STRING, validity,
                                chars=jnp.zeros((c.capacity, 1), jnp.uint8),
                                lengths=jnp.zeros(c.capacity, jnp.int32))
        w = max(target, 1)
        lp = max(len(pad), 1)
        pad_arr = jnp.asarray(np.frombuffer(pad.ljust(1, b"\0"), np.uint8))
        j = jnp.arange(w)[None, :]
        pad_idx = (j - c.lengths[:, None]) % lp
        padded = jnp.take(pad_arr, pad_idx)
        src = (_pad_to(c.chars, w)[:, :w] if c.width
               else jnp.zeros((c.capacity, w), jnp.uint8))
        out = jnp.where(j < c.lengths[:, None], src, padded)
        out_len = jnp.full(c.capacity, target, jnp.int32)
        mask = j < out_len[:, None]
        return DeviceColumn(T.STRING, validity,
                            chars=jnp.where(mask, out, 0).astype(jnp.uint8),
                            lengths=out_len)


class StringRepeat(BinaryExpression):
    """repeat(str, n) with literal n."""

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = True

    def do_columnar_eval(self, ctx, cols):
        from spark_rapids_tpu.expr.base import Literal

        c, _ = cols
        validity = self.and_validity(cols)
        assert isinstance(self.right, Literal)
        n_rep = max(int(self.right.value), 0)
        if n_rep == 0 or c.width == 0:
            return DeviceColumn(T.STRING, validity,
                                chars=jnp.zeros((c.capacity, 1), jnp.uint8),
                                lengths=jnp.zeros(c.capacity, jnp.int32))
        w = c.width * n_rep
        j = jnp.arange(w)[None, :]
        safe_len = jnp.maximum(c.lengths, 1)[:, None]
        src_idx = j % safe_len
        out = jnp.take_along_axis(_pad_to(c.chars, w),
                                  jnp.clip(src_idx, 0, w - 1), axis=1)
        out_len = (c.lengths * n_rep).astype(jnp.int32)
        mask = j < out_len[:, None]
        return DeviceColumn(T.STRING, validity,
                            chars=jnp.where(mask, out, 0).astype(jnp.uint8),
                            lengths=out_len)


class ConcatWs(Expression):
    """concat_ws(sep, s1, s2, ...): null inputs are SKIPPED (not null-
    propagating like concat); null only when the separator is null (the
    TPU path requires a non-null literal sep via overrides)."""

    def __init__(self, children: List[Expression]):
        super().__init__(children)

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = self.children[0].nullable

    def do_columnar_eval(self, ctx, cols):
        sep = cols[0]
        pieces = cols[1:]
        n = sep.capacity
        total_w = (sum(max(c.width, 1) for c in pieces)
                   + max(sep.width, 1) * max(len(pieces) - 1, 0))
        out = jnp.zeros((n, total_w), jnp.uint8)
        out_len = jnp.zeros(n, jnp.int32)
        has_prev = jnp.zeros(n, jnp.bool_)
        for c in pieces:
            include = c.validity
            emit_sep = has_prev & include
            for part, emit, plen in ((sep, emit_sep, sep.lengths),
                                     (c, include, c.lengths)):
                if part.width == 0:
                    continue
                src_idx = jnp.arange(total_w)[None, :] - out_len[:, None]
                in_range = (src_idx >= 0) & (src_idx < part.width)
                src = jnp.take_along_axis(
                    _pad_to(part.chars, total_w),
                    jnp.clip(src_idx, 0, total_w - 1), axis=1)
                write = (in_range & (src_idx < plen[:, None])
                         & emit[:, None])
                out = jnp.where(write, src, out)
                out_len = out_len + jnp.where(emit, plen, 0)
            has_prev = has_prev | include
        return DeviceColumn(T.STRING, jnp.ones(n, jnp.bool_),
                            chars=out, lengths=out_len)


# ---------------------------------------------------------------------------
# Regex: RLike over the plan-time-compiled DFA (regex/transpiler.py).
# ---------------------------------------------------------------------------


def run_dfa(c: DeviceColumn, compiled) -> "jnp.ndarray":
    """Run a compiled DFA over every row; -> (n,) bool matched.

    One lax.scan step per byte column: a single gather into the
    (states x 256) table, vectorized across rows — the TPU replacement for
    cuDF's regex VM."""
    import jax

    table = jnp.asarray(compiled.table.reshape(-1))  # (S*256,)
    accept = jnp.asarray(compiled.accept)
    n = c.capacity
    if c.width == 0:
        state = jnp.zeros(n, jnp.int32)
        return accept[state]
    in_str = jnp.arange(c.width)[None, :] < c.lengths[:, None]

    def step(state, xs):
        ch, live = xs
        nxt = jnp.take(table, state * 256 + ch.astype(jnp.int32))
        return jnp.where(live, nxt, state), None

    state, _ = jax.lax.scan(step, jnp.zeros(n, jnp.int32),
                            (c.chars.T, in_str.T))
    return accept[state]


class RLike(BinaryExpression):
    """str RLIKE pattern (literal).  Pattern is transpiled to a DFA at plan
    time; unsupported patterns are rejected by the overrides layer (the
    reference's CudfRegexTranspiler-reject path)."""

    def _resolve_type(self):
        self._dataType = T.BOOLEAN
        self._nullable = True

    def _compiled(self):
        from spark_rapids_tpu.expr.base import Literal
        from spark_rapids_tpu.regex import compile_regex

        cached = getattr(self, "_dfa", None)
        if cached is None:
            assert isinstance(self.right, Literal)
            cached = self._dfa = compile_regex(self.right.value)
        return cached

    def do_columnar_eval(self, ctx, cols):
        s, _ = cols
        return DeviceColumn(T.BOOLEAN, s.validity,
                            data=run_dfa(s, self._compiled()))


class OctetLength(UnaryExpression):
    """octet_length(str): byte count (the padded layout stores it directly)."""

    def _resolve_type(self):
        self._dataType = T.INT
        self._nullable = self.child.nullable

    def do_columnar_eval(self, ctx, cols):
        c = cols[0]
        return DeviceColumn(T.INT, c.validity, data=c.lengths)


class BitLength(UnaryExpression):
    def _resolve_type(self):
        self._dataType = T.INT
        self._nullable = self.child.nullable

    def do_columnar_eval(self, ctx, cols):
        c = cols[0]
        return DeviceColumn(T.INT, c.validity, data=c.lengths * 8)


class _LeftRight(BinaryExpression):
    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = True

    def do_columnar_eval(self, ctx, cols):
        c, k = cols
        n = c.lengths
        want = k.data.astype(jnp.int32)
        take_n = jnp.clip(jnp.where(want < 0, 0, want), 0, n)
        start = self._start(n, take_n)
        width = c.width
        idx = start[:, None] + jnp.arange(width)[None, :]
        keep = jnp.arange(width)[None, :] < take_n[:, None]
        gathered = jnp.take_along_axis(c.chars, jnp.clip(idx, 0, width - 1),
                                       axis=1)
        return DeviceColumn(T.STRING, c.validity & k.validity,
                            chars=jnp.where(keep, gathered, 0).astype(jnp.uint8),
                            lengths=take_n)


class StringLeft(_LeftRight):
    """left(str, n): first n bytes (ASCII-exact; see Substring caveat)."""

    def _start(self, n, take_n):
        return jnp.zeros_like(n)


class StringRight(_LeftRight):
    """right(str, n): last n bytes."""

    def _start(self, n, take_n):
        return n - take_n


class SubstringIndex(Expression):
    """substring_index(str, delim, count) with a LITERAL delimiter.

    count > 0: everything before the count-th occurrence (whole string if
    fewer); count < 0: everything after the |count|-th occurrence from the
    right; count = 0 or empty delim -> empty string."""

    def __init__(self, s: Expression, delim: Expression, count: Expression):
        super().__init__([s, delim, count])

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = True

    def do_columnar_eval(self, ctx, cols):
        from spark_rapids_tpu.expr.base import Literal

        c, _, k = cols
        delim_expr = self.children[1]
        delim = (str(delim_expr.value).encode("utf-8")
                 if isinstance(delim_expr, Literal)
                 and delim_expr.value is not None else b"")
        width = c.width
        n = c.lengths
        count = k.data.astype(jnp.int32)
        validity = c.validity & cols[1].validity & k.validity
        if len(delim) == 0:
            return DeviceColumn(T.STRING, validity,
                                chars=jnp.zeros_like(c.chars),
                                lengths=jnp.zeros_like(n))
        if len(delim) > width:
            # delimiter longer than every string: no occurrence anywhere ->
            # whole string (count != 0) / empty (count == 0)
            out_len = jnp.where(count == 0, 0, n)
            keep = jnp.arange(width)[None, :] < out_len[:, None]
            return DeviceColumn(T.STRING, validity,
                                chars=jnp.where(keep, c.chars, 0
                                                ).astype(jnp.uint8),
                                lengths=out_len.astype(jnp.int32))
        dl = len(delim)
        # occurrence start positions: delim bytes match AND fully in bounds.
        # Spark counts LEFT-TO-RIGHT NON-OVERLAPPING occurrences for both
        # signs (StringUtils.ordinalIndexOf / lastOrdinalIndexOf are
        # non-overlapping scans).
        hit = jnp.ones((c.capacity, width), jnp.bool_)
        for j, b in enumerate(delim):
            shifted = jnp.roll(c.chars, -j, axis=1) if j else c.chars
            hit = hit & (shifted == b)
        pos_ok = (jnp.arange(width)[None, :] + dl) <= n[:, None]
        hit = hit & pos_ok
        if dl > 1:
            # kill overlapping hits: scan left->right, a hit only counts if
            # no counted hit began in the previous dl-1 positions
            def step(carry, x):
                # carry: distance since last counted hit (>= dl means free)
                free = carry >= dl
                counted = x & free
                nc = jnp.where(counted, 1, carry + 1)
                return nc, counted

            init = jnp.full(c.capacity, dl, jnp.int32)
            _, counted_t = jax.lax.scan(step, init, hit.T)
            hit = counted_t.T
        occ_idx = jnp.cumsum(hit.astype(jnp.int32), axis=1)  # 1-based count
        total = occ_idx[:, -1]
        # forward: cut before count-th occurrence
        is_kth = hit & (occ_idx == jnp.clip(count, 1, None)[:, None])
        kth_pos = jnp.min(jnp.where(
            is_kth, jnp.arange(width)[None, :], width), axis=1)
        fwd_len = jnp.where((count > 0) & (total >= count), kth_pos, n)
        # backward: cut after the (total+count+1)-th occurrence (count < 0)
        wanted = total + count + 1
        is_kth_b = hit & (occ_idx == jnp.clip(wanted, 1, None)[:, None])
        kth_pos_b = jnp.min(jnp.where(
            is_kth_b, jnp.arange(width)[None, :], width), axis=1)
        bwd_start = jnp.where((count < 0) & (total >= -count),
                              kth_pos_b + dl, 0)
        start = jnp.where(count < 0, bwd_start, 0)
        out_len = jnp.where(count == 0, 0,
                            jnp.where(count > 0, fwd_len, n - start))
        out_len = jnp.clip(out_len, 0, n)
        idx = start[:, None] + jnp.arange(width)[None, :]
        keep = jnp.arange(width)[None, :] < out_len[:, None]
        gathered = jnp.take_along_axis(c.chars, jnp.clip(idx, 0, width - 1),
                                       axis=1)
        return DeviceColumn(T.STRING, validity,
                            chars=jnp.where(keep, gathered, 0).astype(jnp.uint8),
                            lengths=out_len.astype(jnp.int32))


class RegExpReplace(Expression):
    """regexp_replace(str, pattern, replacement) — all matches replaced.

    Pattern + replacement are plan-time literals from the span-safe subset
    (regex/spans.py); replacement is literal bytes (no $group refs).
    Reference analog: GpuRegExpReplace via CudfRegexTranspiler."""

    def __init__(self, s: Expression, pattern: Expression,
                 replacement: Expression):
        super().__init__([s, pattern, replacement])
        self._dfa = None  # stashed by the tag-time check

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = True

    def do_columnar_eval(self, ctx, cols):
        from spark_rapids_tpu.expr.base import Literal
        from spark_rapids_tpu.regex.spans import (
            compile_for_spans,
            greedy_match_starts,
            match_lengths,
        )

        c = cols[0]
        if self._dfa is None:
            self._dfa = compile_for_spans(str(self.children[1].value))
        repl = str(self.children[2].value).encode("utf-8")
        R = len(repl)
        w = c.width
        n = c.lengths
        best = match_lengths(self._dfa, c.chars, n)
        matched, mlen = greedy_match_starts(best, n)
        nz = matched & (mlen > 0)
        # covered[p]: char p consumed by a (non-zero) match — diff array
        cap = c.capacity
        diff = jnp.zeros((cap, w + 2), jnp.int32)
        pcols = jnp.arange(w + 1, dtype=jnp.int32)[None, :]
        starts_idx = jnp.where(nz, pcols, w + 1)
        ends_idx = jnp.where(nz, pcols + mlen, w + 1)
        rows_idx = jnp.arange(cap)[:, None].repeat(w + 1, 1)
        diff = diff.at[rows_idx, starts_idx].add(1, mode="drop")
        diff = diff.at[rows_idx, ends_idx].add(-1, mode="drop")
        covered = jnp.cumsum(diff[:, :w], axis=1) > 0
        keep_char = ~covered & (jnp.arange(w)[None, :] < n[:, None])
        # emissions per position p in [0, w]: R if matched[p], +1 if
        # p < w and keep_char[p]
        emit = matched.astype(jnp.int32) * R
        emit = emit.at[:, :w].add(keep_char.astype(jnp.int32))
        prefix = jnp.cumsum(emit, axis=1) - emit     # exclusive
        out_len = prefix[:, -1] + emit[:, -1]
        out_w = c.width * (R + 1) + R if R else c.width
        from spark_rapids_tpu.columnar.column import (
            DEFAULT_WIDTH_BUCKETS,
            round_up_bucket,
        )

        out_w = round_up_bucket(max(out_w, 1), DEFAULT_WIDTH_BUCKETS)
        out = jnp.zeros((cap, out_w), jnp.uint8)
        # chars land after the (optional) replacement at their position
        char_off = prefix[:, :w] + matched[:, :w].astype(jnp.int32) * R
        char_tgt = jnp.where(keep_char, char_off, out_w)
        rows_w = jnp.arange(cap)[:, None].repeat(w, 1)
        out = out.at[rows_w, char_tgt].set(
            jnp.where(keep_char, c.chars, 0).astype(jnp.uint8), mode="drop")
        # replacement bytes (static unroll over R)
        rows_w1 = jnp.arange(cap)[:, None].repeat(w + 1, 1)
        for r, byte in enumerate(repl):
            tgt = jnp.where(matched, prefix + r, out_w)
            out = out.at[rows_w1, tgt].set(jnp.uint8(byte), mode="drop")
        validity = c.validity & cols[1].validity & cols[2].validity
        return DeviceColumn(T.STRING, validity, chars=out,
                            lengths=out_len.astype(jnp.int32))


class RegExpExtract(Expression):
    """regexp_extract(str, pattern, idx) with idx == 0 (the whole match);
    capture groups need a backtracking engine and fall back.

    No match -> empty string (Spark)."""

    def __init__(self, s: Expression, pattern: Expression,
                 idx: Expression):
        super().__init__([s, pattern, idx])
        self._dfa = None

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = True

    def do_columnar_eval(self, ctx, cols):
        from spark_rapids_tpu.regex.spans import (
            compile_for_spans,
            match_lengths,
        )

        c = cols[0]
        if self._dfa is None:
            self._dfa = compile_for_spans(str(self.children[1].value))
        w = c.width
        n = c.lengths
        best = match_lengths(self._dfa, c.chars, n)
        has = best >= 0
        first = jnp.argmax(has, axis=1).astype(jnp.int32)
        found = jnp.any(has, axis=1)
        mlen = jnp.where(found,
                         jnp.take_along_axis(best, first[:, None],
                                             axis=1)[:, 0], 0)
        idx = first[:, None] + jnp.arange(w)[None, :]
        keep = jnp.arange(w)[None, :] < mlen[:, None]
        gathered = jnp.take_along_axis(c.chars, jnp.clip(idx, 0, w - 1),
                                       axis=1)
        validity = c.validity & cols[1].validity & cols[2].validity
        return DeviceColumn(T.STRING, validity,
                            chars=jnp.where(keep, gathered, 0).astype(jnp.uint8),
                            lengths=jnp.where(found, mlen, 0).astype(jnp.int32))


def _java_split(rx, s: str, limit: int):
    """Java String.split semantics: limit>0 caps the part count (limit=1
    -> no split at all); limit==0 drops TRAILING empty strings; negative
    limits keep them."""
    if limit == 1:
        return [s]
    parts = rx.split(s, maxsplit=(limit - 1 if limit > 0 else 0))
    if limit == 0:
        while parts and parts[-1] == "":
            parts.pop()
    return parts


class StringSplit(Expression):
    """split(str, regex[, limit]) -> array<string> (3-D char tensor).

    Reference analog: GpuStringSplit via the regex transpiler
    (RegexParser.scala consumers).  Irregular per-row output shapes make
    this a host kernel (like the JSON family); the pattern is validated
    at plan time and translated with the same Java-regex rules the oracle
    uses for RLike."""

    is_host_kernel = True

    def __init__(self, s: Expression, pattern: Expression,
                 limit: Expression = None):
        kids = [s, pattern] + ([limit] if limit is not None else [])
        super().__init__(kids)

    def _resolve_type(self):
        self._dataType = T.ArrayType(T.STRING, containsNull=False)
        self._nullable = True
        from spark_rapids_tpu.expr.base import Literal

        self._pattern = None
        self._limit = -1
        if isinstance(self.children[1], Literal) \
                and self.children[1].value is not None:
            self._pattern = str(self.children[1].value)
        if len(self.children) > 2 and isinstance(self.children[2], Literal) \
                and self.children[2].value is not None:
            self._limit = int(self.children[2].value)

    def do_columnar_eval(self, ctx: EvalContext, cols):
        import re as _re

        import numpy as np

        from spark_rapids_tpu.columnar.column import HostColumn

        c = cols[0]
        n = int(ctx.batch.num_rows)  # eager (host kernel) path
        cap = c.capacity
        host = c.to_host(n)
        vals = host.to_pylist()
        from spark_rapids_tpu.cpu.oracle import _java_regex_to_python

        try:
            rx = _re.compile(_java_regex_to_python(self._pattern))
        except _re.error:
            rx = None
        out = []
        for v in vals:
            if v is None or rx is None:
                out.append(None)
                continue
            out.append(_java_split(rx, v, self._limit))
        h = HostColumn.from_pylist(out, self.dataType)
        from spark_rapids_tpu.columnar.column import DeviceColumn

        return DeviceColumn.from_host(h, capacity=cap)


class ArrayJoin(Expression):
    """array_join(arr, delim[, null_replacement])."""

    is_host_kernel = True

    def __init__(self, arr: Expression, delim: Expression,
                 null_replacement: Expression = None):
        kids = [arr, delim] + ([null_replacement]
                               if null_replacement is not None else [])
        super().__init__(kids)

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = True

    def do_columnar_eval(self, ctx: EvalContext, cols):
        from spark_rapids_tpu.columnar.column import DeviceColumn, HostColumn

        arr, delim = cols[0], cols[1]
        nullrep = cols[2] if len(cols) > 2 else None
        n = int(ctx.batch.num_rows)
        cap = arr.capacity
        rows = arr.to_host(n).to_pylist()
        delims = delim.to_host(n).to_pylist()
        reps = nullrep.to_host(n).to_pylist() if nullrep is not None \
            else [None] * n
        out = []
        for row, d, rep in zip(rows, delims, reps):
            if row is None or d is None:
                out.append(None)
                continue
            parts = [e if e is not None else rep for e in row]
            out.append(d.join(p for p in parts if p is not None))
        h = HostColumn.from_pylist(out, T.STRING)
        return DeviceColumn.from_host(h, capacity=cap)


class RegExpExtractAll(Expression):
    """regexp_extract_all(str, pattern[, idx=0]) -> array<string> of all
    non-overlapping leftmost matches.

    Tag-time contract (checked in overrides): span-safe pattern with
    bounded, non-empty match length (min>=1, max<=MAX_MATCH_LEN) so the
    padded element matrix stays static; rows with more than MAX_MATCHES
    matches raise via the error flags instead of truncating silently."""

    MAX_MATCH_LEN = 32
    MAX_MATCHES = 64

    def __init__(self, s: Expression, pattern: Expression,
                 idx: Expression = None):
        from spark_rapids_tpu.expr.base import Literal

        super().__init__([s, pattern]
                         + ([idx] if idx is not None else
                            [Literal(0, T.INT)]))
        self._dfa = None
        self._bounds = None

    def _resolve_type(self):
        self._dataType = T.ArrayType(T.STRING, containsNull=False)
        self._nullable = True

    def sql_string(self):
        return (f"regexp_extract_all({self.children[0].sql_string()}, "
                f"{self.children[1].sql_string()})")

    def do_columnar_eval(self, ctx, cols):
        from spark_rapids_tpu.regex.spans import (
            compile_for_spans,
            greedy_match_starts,
            match_lengths,
        )

        c = cols[0]
        if self._dfa is None:
            self._dfa = compile_for_spans(str(self.children[1].value))
        cap, w = c.capacity, c.width
        n = c.lengths
        best = match_lengths(self._dfa, c.chars, n)
        matched, mlen = greedy_match_starts(best, n)
        # positions span [0, w] (a zero-length match may sit at the end);
        # bounded non-empty matches only start inside the string
        nz = (matched & (mlen > 0))[:, :w]
        mlen = mlen[:, :w]
        ecount = jnp.sum(nz, axis=1).astype(jnp.int32)
        maxe = min(self.MAX_MATCHES, max(w, 1))
        ctx.add_error(c.validity & (ecount > maxe),
                      f"regexp_extract_all: more than {self.MAX_MATCHES} "
                      f"matches in one string")
        eidx = (jnp.cumsum(nz.astype(jnp.int32), axis=1) - 1)
        rows = jnp.arange(cap)[:, None].repeat(w, 1)
        tgt = jnp.where(nz, jnp.clip(eidx, 0, maxe - 1), maxe)
        pos = jnp.arange(w, dtype=jnp.int32)[None, :].repeat(cap, 0)
        starts_e = jnp.zeros((cap, maxe), jnp.int32).at[rows, tgt].set(
            pos, mode="drop")
        mlen_e = jnp.zeros((cap, maxe), jnp.int32).at[rows, tgt].set(
            jnp.where(nz, mlen, 0), mode="drop")
        ew = min(self.MAX_MATCH_LEN, max(w, 1))
        k = jnp.arange(ew, dtype=jnp.int32)[None, None, :]
        src = jnp.clip(starts_e[:, :, None] + k, 0, w - 1)
        chars3 = jnp.take_along_axis(
            c.chars[:, None, :].repeat(maxe, 1), src, axis=2)
        inlen = k < mlen_e[:, :, None]
        chars3 = jnp.where(inlen, chars3, 0).astype(jnp.uint8)
        elem_valid = (jnp.arange(maxe, dtype=jnp.int32)[None, :]
                      < ecount[:, None])
        validity = c.validity & cols[1].validity
        return DeviceColumn(self.dataType, validity, chars=chars3,
                            data=mlen_e, lengths=jnp.minimum(ecount, maxe),
                            elem_valid=elem_valid)


class Overlay(Expression):
    """overlay(input, replace, pos[, len]) — 1-based; len<0 means
    length(replace) (Spark default)."""

    def __init__(self, s, r, pos, length=None):
        from spark_rapids_tpu.expr.base import Literal

        super().__init__([s, r, pos]
                         + ([length] if length is not None
                            else [Literal(-1, T.INT)]))

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = True

    def sql_string(self):
        return ("overlay("
                + ", ".join(c.sql_string() for c in self.children) + ")")

    def do_columnar_eval(self, ctx, cols):
        s, r, p, ln = cols
        cap = s.capacity
        pos0 = (p.data.astype(jnp.int32) - 1)
        rl = r.lengths
        replen = jnp.where(ln.data.astype(jnp.int32) < 0, rl,
                           ln.data.astype(jnp.int32))
        pre_len = jnp.clip(pos0, 0, s.lengths)
        tail_start = jnp.clip(pos0 + replen, 0, s.lengths)
        tail_len = s.lengths - tail_start
        out_len = pre_len + rl + tail_len
        out_w = int(s.width + r.width)
        from spark_rapids_tpu.columnar.column import (
            DEFAULT_WIDTH_BUCKETS,
            round_up_bucket,
        )

        out_w = round_up_bucket(max(out_w, 1), DEFAULT_WIDTH_BUCKETS)
        pos_o = jnp.arange(out_w, dtype=jnp.int32)[None, :]
        # three segments gathered by source index
        in_pre = pos_o < pre_len[:, None]
        in_rep = ~in_pre & (pos_o < (pre_len + rl)[:, None])
        in_tail = ~in_pre & ~in_rep & (pos_o < out_len[:, None])
        src_s = jnp.where(in_pre, pos_o,
                          jnp.where(in_tail,
                                    pos_o - (pre_len + rl)[:, None]
                                    + tail_start[:, None], 0))
        src_r = jnp.where(in_rep, pos_o - pre_len[:, None], 0)
        sw = max(s.width, 1)
        rw = max(r.width, 1)
        g_s = jnp.take_along_axis(
            s.chars if s.width else jnp.zeros((cap, 1), jnp.uint8),
            jnp.clip(src_s, 0, sw - 1), axis=1)
        g_r = jnp.take_along_axis(
            r.chars if r.width else jnp.zeros((cap, 1), jnp.uint8),
            jnp.clip(src_r, 0, rw - 1), axis=1)
        chars = jnp.where(in_rep, g_r,
                          jnp.where(in_pre | in_tail, g_s, 0))
        validity = s.validity & r.validity & p.validity & ln.validity
        return DeviceColumn(T.STRING, validity,
                            chars=chars.astype(jnp.uint8),
                            lengths=out_len.astype(jnp.int32))


class FindInSet(BinaryExpression):
    """find_in_set(s, comma_list) — 1-based index, 0 when absent or when s
    contains a comma."""

    def _resolve_type(self):
        self._dataType = T.INT
        self._nullable = True

    def do_columnar_eval(self, ctx, cols):
        s, lst = cols
        cap = s.capacity
        w = max(lst.width, 1)
        pos = jnp.arange(w, dtype=jnp.int32)[None, :]
        in_l = pos < lst.lengths[:, None]
        lch = jnp.where(in_l, lst.chars, 0) if lst.width else \
            jnp.zeros((cap, 1), jnp.uint8)
        is_comma = (lch == ord(",")) & in_l
        # element id per position (elements are the runs between commas)
        elem = jnp.cumsum(is_comma.astype(jnp.int32), axis=1) - \
            is_comma.astype(jnp.int32)
        rows = jnp.arange(cap)[:, None].repeat(w, 1)
        # per-element char count + first position via scatter-reduce
        maxe = w + 1
        one_hot_src = jnp.where(in_l & ~is_comma, elem, maxe)
        counts = jnp.zeros((cap, maxe + 1), jnp.int32).at[
            rows, jnp.clip(one_hot_src, 0, maxe)].add(
            jnp.where(in_l & ~is_comma, 1, 0), mode="drop")
        counts = counts[:, :maxe]
        first_pos = jnp.full((cap, maxe + 1), w, jnp.int32).at[
            rows, jnp.clip(jnp.where(in_l & ~is_comma, elem, maxe),
                           0, maxe)].min(
            jnp.where(in_l & ~is_comma, pos, w), mode="drop")
        first_pos = first_pos[:, :maxe]
        nelem = jnp.sum(is_comma.astype(jnp.int32), axis=1) + 1
        # compare s against each element (element count = comma count + 1)
        slen = s.lengths
        sw = max(s.width, 1)
        sch = s.chars if s.width else jnp.zeros((cap, 1), jnp.uint8)
        s_has_comma = jnp.any((sch == ord(",")) &
                              (jnp.arange(sw)[None, :] < slen[:, None]),
                              axis=1)
        k = jnp.arange(sw, dtype=jnp.int32)[None, None, :]
        src = jnp.clip(first_pos[:, :, None] + k, 0, w - 1)
        echars = jnp.take_along_axis(lch[:, None, :].repeat(maxe, 1), src,
                                     axis=2)
        want = sch[:, None, :]
        cmp_len = jnp.minimum(counts, slen[:, None])
        eq = jnp.all(jnp.where(k < cmp_len[:, :, None], echars == want,
                               True), axis=2)
        match = eq & (counts == slen[:, None]) & \
            (jnp.arange(maxe, dtype=jnp.int32)[None, :] < nelem[:, None])
        found = jnp.any(match, axis=1)
        idx = jnp.argmax(match, axis=1).astype(jnp.int32) + 1
        res = jnp.where(found & ~s_has_comma, idx, 0)
        return DeviceColumn(T.INT, s.validity & lst.validity, data=res)


class Elt(Expression):
    """elt(n, s1, s2, ...) — 1-based pick; out of range -> null."""

    def __init__(self, children):
        super().__init__(list(children))

    def sql_string(self):
        return "elt(" + ", ".join(c.sql_string() for c in self.children) + ")"

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = True

    def do_columnar_eval(self, ctx, cols):
        n = cols[0]
        opts = cols[1:]
        cap = n.capacity
        w = max(max((c.width for c in opts), default=1), 1)
        from spark_rapids_tpu.expr.predicates import _pad_to

        idx = n.data.astype(jnp.int32)
        chars = jnp.zeros((cap, w), jnp.uint8)
        lengths = jnp.zeros(cap, jnp.int32)
        validity = jnp.zeros(cap, jnp.bool_)
        for k, c in enumerate(opts):
            takes = idx == (k + 1)
            chars = jnp.where(takes[:, None], _pad_to(c.chars, w), chars)
            lengths = jnp.where(takes, c.lengths, lengths)
            validity = jnp.where(takes, c.validity, validity)
        return DeviceColumn(T.STRING, n.validity & validity,
                            chars=chars, lengths=lengths)


class StringSpace(UnaryExpression):
    """space(n) — n spaces (n<0 -> empty).  A literal n sizes the char
    matrix exactly; non-literal n pays the MAX_LEN-wide bucket and rows
    above MAX_LEN raise via the error flags."""

    MAX_LEN = 2048

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = self.child.nullable

    def do_columnar_eval(self, ctx, cols):
        from spark_rapids_tpu.columnar.column import (
            DEFAULT_WIDTH_BUCKETS,
            round_up_bucket,
        )
        from spark_rapids_tpu.expr.base import Literal

        c = cols[0]
        n = jnp.maximum(c.data.astype(jnp.int32), 0)
        if isinstance(self.child, Literal) and self.child.value is not None:
            w_static = round_up_bucket(
                min(max(int(self.child.value), 1), self.MAX_LEN),
                DEFAULT_WIDTH_BUCKETS)
        else:
            w_static = round_up_bucket(self.MAX_LEN, DEFAULT_WIDTH_BUCKETS)
        ctx.add_error(c.validity & (n > w_static),
                      f"space(): length above {self.MAX_LEN}")
        n = jnp.minimum(n, w_static)
        pos = jnp.arange(w_static, dtype=jnp.int32)[None, :]
        chars = jnp.where(pos < n[:, None], jnp.uint8(ord(" ")),
                          jnp.uint8(0))
        return DeviceColumn(T.STRING, c.validity, chars=chars, lengths=n)


class StringTrimLeft(UnaryExpression):
    """ltrim(s) — strips leading spaces (Spark trims 0x20 only)."""

    side = "left"

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = self.child.nullable

    def do_columnar_eval(self, ctx, cols):
        c = cols[0]
        pos = jnp.arange(c.width)[None, :]
        in_str = pos < c.lengths[:, None]
        nonws = in_str & (c.chars != ord(" "))
        any_nonws = jnp.any(nonws, axis=1)
        if self.side == "left":
            first = jnp.where(any_nonws, jnp.argmax(nonws, axis=1), 0)
            out_len = jnp.where(any_nonws, c.lengths - first, 0)
        else:
            first = jnp.zeros(c.capacity, jnp.int32)
            last = jnp.where(
                any_nonws,
                c.width - 1 - jnp.argmax(nonws[:, ::-1], axis=1), -1)
            out_len = (last + 1).astype(jnp.int32)
        idx = first[:, None] + jnp.arange(c.width)[None, :]
        take = jnp.arange(c.width)[None, :] < out_len[:, None]
        gathered = jnp.take_along_axis(
            c.chars, jnp.clip(idx, 0, max(c.width - 1, 0)), axis=1)
        return DeviceColumn(T.STRING, c.validity,
                            chars=jnp.where(take, gathered,
                                            0).astype(jnp.uint8),
                            lengths=out_len.astype(jnp.int32))


class StringTrimRight(StringTrimLeft):
    """rtrim(s)."""

    side = "right"


class Mask(Expression):
    """mask(s[, upper[, lower[, digit[, other]]]]) — literal replacement
    chars; NULL keeps the class, '\\0' sentinel not supported."""

    def __init__(self, s, upper=None, lower=None, digit=None, other=None):
        from spark_rapids_tpu.expr.base import Literal

        def lit_or(v, dflt):
            return v if v is not None else Literal(dflt, T.STRING)

        super().__init__([s, lit_or(upper, "X"), lit_or(lower, "x"),
                          lit_or(digit, "n"),
                          other if other is not None
                          else Literal(None, T.STRING)])

    def sql_string(self):
        return "mask(" + ", ".join(c.sql_string() for c in self.children) + ")"

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = True

    def do_columnar_eval(self, ctx, cols):
        c = cols[0]

        def rep_of(i):
            e = self.children[i]
            v = getattr(e, "value", None)
            return None if v is None else ord(str(v)[0])

        up, lo, dg, ot = (rep_of(1), rep_of(2), rep_of(3), rep_of(4))
        ch = c.chars
        out = ch
        is_up = (ch >= ord("A")) & (ch <= ord("Z"))
        is_lo = (ch >= ord("a")) & (ch <= ord("z"))
        is_dg = (ch >= ord("0")) & (ch <= ord("9"))
        if up is not None:
            out = jnp.where(is_up, jnp.uint8(up), out)
        if lo is not None:
            out = jnp.where(is_lo, jnp.uint8(lo), out)
        if dg is not None:
            out = jnp.where(is_dg, jnp.uint8(dg), out)
        if ot is not None:
            out = jnp.where(~(is_up | is_lo | is_dg), jnp.uint8(ot), out)
        return DeviceColumn(T.STRING, c.validity,
                            chars=out.astype(jnp.uint8),
                            lengths=c.lengths)


class ILike(Like):
    """ILIKE — case-insensitive LIKE: ascii-lower BOTH the data and the
    pattern, then the same compiled-literal machinery."""

    def do_columnar_eval(self, ctx, cols):
        from spark_rapids_tpu.expr.base import Literal

        s, p = cols
        lower = jnp.where((s.chars >= ord("A")) & (s.chars <= ord("Z")),
                          s.chars + 32, s.chars).astype(jnp.uint8)
        sl = DeviceColumn(T.STRING, s.validity, chars=lower,
                          lengths=s.lengths)
        low = getattr(self, "_low", None)
        if low is None:
            low = Like(self.children[0],
                       Literal(str(self.right.value).lower(), T.STRING))
            low._dataType = T.BOOLEAN
            low.resolved = True
            if getattr(self, "_compiled", None) is not None:
                low._compiled = self._compiled  # tag-time DFA, reused
            self._low = low
        return low.do_columnar_eval(ctx, [sl, p])


class _RegExpSpanBase(Expression):
    """Shared span scan for regexp_count / regexp_instr / regexp_substr."""

    def __init__(self, s, pattern):
        super().__init__([s, pattern])
        self._dfa = None

    def _spans(self, cols):
        from spark_rapids_tpu.regex.spans import (compile_for_spans,
                                                  greedy_match_starts,
                                                  match_lengths)

        c = cols[0]
        if self._dfa is None:
            self._dfa = compile_for_spans(str(self.children[1].value))
        best = match_lengths(self._dfa, c.chars, c.lengths)
        matched, mlen = greedy_match_starts(best, c.lengths)
        return c, matched, mlen


class RegExpCount(_RegExpSpanBase):
    """regexp_count(s, pattern) — non-overlapping match count."""

    def _resolve_type(self):
        self._dataType = T.INT
        self._nullable = True

    def sql_string(self):
        return (f"regexp_count({self.children[0].sql_string()}, "
                f"{self.children[1].sql_string()})")

    def do_columnar_eval(self, ctx, cols):
        c, matched, mlen = self._spans(cols)
        n = jnp.sum((matched & (mlen > 0)).astype(jnp.int32), axis=1)
        return DeviceColumn(T.INT, c.validity & cols[1].validity, data=n)


class RegExpInStr(_RegExpSpanBase):
    """regexp_instr(s, pattern) — 1-based position of the first match,
    0 when absent."""

    def _resolve_type(self):
        self._dataType = T.INT
        self._nullable = True

    def sql_string(self):
        return (f"regexp_instr({self.children[0].sql_string()}, "
                f"{self.children[1].sql_string()})")

    def do_columnar_eval(self, ctx, cols):
        c, matched, mlen = self._spans(cols)
        nz = matched & (mlen > 0)
        found = jnp.any(nz, axis=1)
        pos = jnp.argmax(nz, axis=1).astype(jnp.int32) + 1
        return DeviceColumn(T.INT, c.validity & cols[1].validity,
                            data=jnp.where(found, pos, 0))


class RegExpSubStr(_RegExpSpanBase):
    """regexp_substr(s, pattern) — first match, NULL when absent."""

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = True

    def sql_string(self):
        return (f"regexp_substr({self.children[0].sql_string()}, "
                f"{self.children[1].sql_string()})")

    def do_columnar_eval(self, ctx, cols):
        c, matched, mlen = self._spans(cols)
        nz = matched & (mlen > 0)
        found = jnp.any(nz, axis=1)
        first = jnp.argmax(nz, axis=1).astype(jnp.int32)
        w = max(c.width, 1)
        ln = jnp.take_along_axis(mlen, first[:, None], axis=1)[:, 0]
        idx = first[:, None] + jnp.arange(w)[None, :]
        keep = jnp.arange(w)[None, :] < ln[:, None]
        g = jnp.take_along_axis(
            c.chars if c.width else jnp.zeros((c.capacity, 1), jnp.uint8),
            jnp.clip(idx, 0, w - 1), axis=1)
        validity = c.validity & cols[1].validity & found
        return DeviceColumn(T.STRING, validity,
                            chars=jnp.where(keep, g, 0).astype(jnp.uint8),
                            lengths=jnp.where(found, ln, 0).astype(jnp.int32))


class SplitPart(Expression):
    """split_part(s, delim, n) — 1-based field between literal delimiters;
    negative n counts from the end; out of range -> empty string."""

    def __init__(self, s, delim, n):
        super().__init__([s, delim, n])

    def sql_string(self):
        return ("split_part("
                + ", ".join(c.sql_string() for c in self.children) + ")")

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = True

    def do_columnar_eval(self, ctx, cols):
        from spark_rapids_tpu.expr.base import Literal

        s, d, nn = cols
        delim = str(self.children[1].value).encode()
        L = len(delim)
        cap, w = s.capacity, max(s.width, 1)
        ch = s.chars if s.width else jnp.zeros((cap, 1), jnp.uint8)
        pos = jnp.arange(w)[None, :]
        in_str = pos < s.lengths[:, None]
        # delimiter-start mask (non-overlapping, left to right is implied
        # because fields between delim STARTS are what Spark splits on —
        # overlapping delims only arise for self-overlapping literals,
        # which the tag check rejects)
        hit = jnp.ones((cap, w), jnp.bool_)
        for k, byte in enumerate(delim):
            idx = jnp.clip(pos + k, 0, w - 1)
            ok = jnp.take_along_axis(ch, idx, axis=1) == byte
            ok = ok & (pos + k < s.lengths[:, None])
            hit = hit & ok
        hit = hit & in_str
        field = jnp.cumsum(hit.astype(jnp.int32), axis=1)
        # char belongs to field f unless inside a delimiter occurrence
        in_delim = jnp.zeros((cap, w), jnp.bool_)
        for k in range(L):
            src = pos - k
            ok = (src >= 0)
            h = jnp.take_along_axis(hit, jnp.clip(src, 0, w - 1), axis=1)
            in_delim = in_delim | (h & ok)
        nfields = (jnp.max(jnp.where(in_str, field, 0), axis=1) + 1)
        want = nn.data.astype(jnp.int32)
        want = jnp.where(want < 0, nfields + want + 1, want)
        target = want - 1
        fid = field - hit.astype(jnp.int32)  # delim start counts next field
        sel = in_str & ~in_delim & (fid == target[:, None])
        out_len = jnp.sum(sel, axis=1).astype(jnp.int32)
        # compact selected chars to the left
        tgt = jnp.cumsum(sel.astype(jnp.int32), axis=1) - 1
        rows = jnp.arange(cap)[:, None].repeat(w, 1)
        out = jnp.zeros((cap, w), jnp.uint8).at[
            rows, jnp.where(sel, tgt, w)].set(
            jnp.where(sel, ch, 0), mode="drop")
        # out of range -> EMPTY STRING, not null (Spark split_part)
        ok_range = (want >= 1) & (want <= nfields)
        validity = s.validity & d.validity & nn.validity
        return DeviceColumn(T.STRING, validity,
                            chars=jnp.where(ok_range[:, None], out,
                                            0).astype(jnp.uint8),
                            lengths=jnp.where(ok_range, out_len,
                                              0).astype(jnp.int32))


class Luhn(UnaryExpression):
    """luhn_check(s): Luhn mod-10 checksum validity of a digit string.

    Reference analog: GpuLuhnCheck (sql-plugin stringFunctions; SURVEY.md
    §2.5 Strings).  False for empty strings or any non-digit byte."""

    def _resolve_type(self):
        self._dataType = T.BOOLEAN
        self._nullable = self.child.nullable

    def sql_string(self):
        return f"luhn_check({self.child.sql_string()})"

    def do_columnar_eval(self, ctx, cols):
        s = cols[0]
        cap = s.capacity
        if not s.width:
            return DeviceColumn(T.BOOLEAN, s.validity,
                                data=jnp.zeros(cap, jnp.bool_))
        ch = s.chars.astype(jnp.int32)
        w = s.width
        in_str = jnp.arange(w)[None, :] < s.lengths[:, None]
        digit = (ch >= 0x30) & (ch <= 0x39)
        all_digits = jnp.all(digit | ~in_str, axis=1) & (s.lengths > 0)
        d = jnp.where(in_str & digit, ch - 0x30, 0)
        # position from the right (rightmost = 0); double odd positions
        pos_r = s.lengths[:, None] - 1 - jnp.arange(w)[None, :]
        dbl = (pos_r % 2) == 1
        dd = jnp.where(dbl, d * 2, d)
        dd = jnp.where(dd > 9, dd - 9, dd)
        total = jnp.sum(jnp.where(in_str, dd, 0), axis=1)
        ok = all_digits & (total % 10 == 0)
        return DeviceColumn(T.BOOLEAN, s.validity, data=ok)


class Empty2Null(UnaryExpression):
    """empty string -> NULL (Spark inserts this above Hive text writes)."""

    def _resolve_type(self):
        self._dataType = T.STRING
        self._nullable = True

    def sql_string(self):
        return f"empty2null({self.child.sql_string()})"

    def do_columnar_eval(self, ctx, cols):
        c = cols[0]
        return DeviceColumn(T.STRING, c.validity & (c.lengths > 0),
                            chars=c.chars, lengths=c.lengths)
