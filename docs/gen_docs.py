"""Generate docs/supported_ops.md and docs/configs.md from the registries.

Reference analog: SupportedOpsDocs.scala + the RapidsConf doc generator that
emits docs/supported_ops.md and configs.md (SURVEY.md §2.2/§5.6).  Run:

    python docs/gen_docs.py          # rewrites both files

tests/test_docs_gen.py re-generates in CI and fails on drift.
"""
from __future__ import annotations

import os

HERE = os.path.dirname(os.path.abspath(__file__))


def gen_supported_ops() -> str:
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.overrides.overrides import EXECS, EXPRESSIONS

    kind_names = [
        ("BOOLEAN", T.BooleanType), ("BYTE", T.ByteType),
        ("SHORT", T.ShortType), ("INT", T.IntegerType),
        ("LONG", T.LongType), ("FLOAT", T.FloatType),
        ("DOUBLE", T.DoubleType), ("DECIMAL", T.DecimalType),
        ("STRING", T.StringType), ("DATE", T.DateType),
        ("TIMESTAMP", T.TimestampType), ("ARRAY", T.ArrayType),
    ]
    out = ["# Supported expressions and execs",
           "",
           "Generated by `docs/gen_docs.py` from the TpuOverrides "
           "registries — do not edit by hand.",
           "Every entry has a kill switch: "
           "`spark.rapids.sql.expression.<Name>=false` / "
           "`spark.rapids.sql.exec.<Name>=false`.",
           ""]

    def sig_cells(sig):
        cells = []
        for label, cls in kind_names:
            if cls in sig.kinds:
                note = sig.notes.get(cls)
                if cls is T.DecimalType and sig.max_decimal_precision < 38:
                    note = (note + "; " if note else "") + \
                        f"max precision {sig.max_decimal_precision}"
                cells.append("S*" if note else "S")
            else:
                cells.append("")
        return cells

    def notes_of(sig):
        parts = []
        for label, cls in kind_names:
            note = sig.notes.get(cls)
            if cls is T.DecimalType and cls in sig.kinds \
                    and sig.max_decimal_precision < 38:
                note = (note + "; " if note else "") + \
                    f"max precision {sig.max_decimal_precision}"
            if note and cls in sig.kinds:
                parts.append(f"{label}: {note}")
        return "; ".join(parts)

    header = "| Name | " + " | ".join(k for k, _ in kind_names) + \
        " | Notes |"
    sep = "|" + "---|" * (len(kind_names) + 2)

    out += [f"## Expressions ({len(EXPRESSIONS)})", "", header, sep]
    for cls in sorted(EXPRESSIONS, key=lambda c: c.__name__):
        rule = EXPRESSIONS[cls]
        cells = sig_cells(rule.type_sig)
        out.append(f"| {cls.__name__} | " + " | ".join(cells)
                   + f" | {notes_of(rule.type_sig)} |")
    out += ["", f"## Execs ({len(EXECS)})", "", header, sep]
    for cls in sorted(EXECS, key=lambda c: c.__name__):
        rule = EXECS[cls]
        cells = sig_cells(rule.type_sig)
        note = notes_of(rule.type_sig)
        if rule.desc:
            note = (rule.desc + ("; " + note if note else ""))
        out.append(f"| {cls.__name__} | " + " | ".join(cells)
                   + f" | {note} |")

    from spark_rapids_tpu.overrides.transitions import stage_rules

    rules = stage_rules()
    out += ["", f"## Transition stages ({len(rules)})", "",
            "Collective (ICI mesh) and fused execs installed by plan "
            "rewrite (`TpuTransitionOverrides`) rather than node "
            "conversion.  Each reports install/fallback through "
            "`spark.rapids.sql.explain` like any exec; the kill switch "
            "is the listed conf.",
            "", "| Name | Kill switch | Replaces |", "|---|---|---|"]
    for name in sorted(rules):
        r = rules[name]
        out.append(f"| {r.name} | `{r.conf_key}` | {r.desc} |")
    out.append("")
    return "\n".join(out)


def gen_configs() -> str:
    # import every module that registers confs so the registry is complete
    # regardless of what else the process has loaded
    import spark_rapids_tpu.io.writer  # noqa: F401
    import spark_rapids_tpu.memory.device_manager  # noqa: F401
    import spark_rapids_tpu.memory.spill  # noqa: F401
    import spark_rapids_tpu.shuffle.manager  # noqa: F401

    from spark_rapids_tpu.config import _REGISTRY

    out = ["# Configuration",
           "",
           "Generated by `docs/gen_docs.py` from the typed conf registry "
           "(RapidsConf analog) — do not edit by hand.",
           "",
           f"{len(_REGISTRY)} configs.  Per-op kill switches "
           "(`spark.rapids.sql.expression.*` / `spark.rapids.sql.exec.*`) "
           "are dynamic and not listed individually.",
           "",
           "| Key | Default | Description |",
           "|---|---|---|"]
    # a default under the checkout reads the same wherever the checkout is
    checkout = os.path.dirname(HERE)
    for key in sorted(_REGISTRY):
        e = _REGISTRY[key]
        default = getattr(e, "default", None)
        if isinstance(default, str) and default.startswith(checkout):
            default = "<checkout>" + default[len(checkout):]
        doc = (e.doc or "").replace("\n", " ")
        out.append(f"| `{key}` | `{default}` | {doc} |")
    out.append("")
    return "\n".join(out)


def main():
    with open(os.path.join(HERE, "supported_ops.md"), "w") as f:
        f.write(gen_supported_ops())
    with open(os.path.join(HERE, "configs.md"), "w") as f:
        f.write(gen_configs())
    print("wrote docs/supported_ops.md and docs/configs.md")


if __name__ == "__main__":
    main()
