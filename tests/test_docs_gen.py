"""Docs generation drift check (reference: SupportedOpsDocs + configs.md
generation verified in CI)."""
import os


def test_generated_docs_are_current():
    import sys
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(here, "docs"))
    import gen_docs

    with open(os.path.join(here, "docs", "supported_ops.md")) as f:
        assert f.read() == gen_docs.gen_supported_ops(), \
            "docs/supported_ops.md is stale — run python docs/gen_docs.py"
    with open(os.path.join(here, "docs", "configs.md")) as f:
        assert f.read() == gen_docs.gen_configs(), \
            "docs/configs.md is stale — run python docs/gen_docs.py"


def test_registry_minimums():
    from spark_rapids_tpu.overrides.overrides import EXECS, EXPRESSIONS

    assert len(EXPRESSIONS) >= 120, len(EXPRESSIONS)
    assert len(EXECS) >= 18, len(EXECS)


def _unread_confs(repo):
    """Keys of ``config._REGISTRY`` that no code under spark_rapids_tpu/
    (outside config.py and analysis/rules_docs.py) reads: not by the
    constant, not by the key (spelled out or built by an f-string, as
    overrides.py builds ``...format.{fmt}.write.enabled``), not through
    an accessor of TpuConf.  Docstrings, comments and docs are not
    reads."""
    import ast
    import re

    from spark_rapids_tpu import config as C

    pkg = os.path.join(repo, "spark_rapids_tpu")
    skip = {os.path.join(pkg, "config.py"),
            os.path.join(pkg, "analysis", "rules_docs.py")}
    names, attrs, strings, patterns = set(), set(), set(), []
    for dirpath, _, files in os.walk(pkg):
        for fn in files:
            path = os.path.join(dirpath, fn)
            if not fn.endswith(".py") or path in skip:
                continue
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Name):
                    names.add(node.id)
                elif isinstance(node, ast.Attribute):
                    attrs.add(node.attr)
                elif isinstance(node, ast.Constant) \
                        and isinstance(node.value, str):
                    strings.add(node.value)
                elif isinstance(node, ast.JoinedStr):
                    patterns.append(re.compile("".join(
                        re.escape(v.value) if isinstance(v, ast.Constant)
                        else r"\w+" for v in node.values)))
    constants = {}
    for name, v in vars(C).items():
        if isinstance(v, C.ConfEntry):
            constants.setdefault(v.key, set()).add(name)
    # an accessor: a method or property of TpuConf that names the constant
    accessors = {}
    with open(os.path.join(pkg, "config.py"), encoding="utf-8") as f:
        tree = ast.parse(f.read())
    cls = next(n for n in tree.body
               if isinstance(n, ast.ClassDef) and n.name == "TpuConf")
    for fn in cls.body:
        if isinstance(fn, ast.FunctionDef):
            used = {n.id for n in ast.walk(fn) if isinstance(n, ast.Name)}
            for key, consts in constants.items():
                if consts & used:
                    accessors.setdefault(key, set()).add(fn.name)
    return sorted(
        key for key in C._REGISTRY
        if not constants.get(key, set()) & (names | attrs)
        and not accessors.get(key, set()) & attrs
        and key not in strings
        and not any(p.fullmatch(key) for p in patterns))


def test_every_conf_is_read_by_the_engine():
    """A conf that nothing reads is a promise in docs/configs.md that the
    engine does not keep (ISSUE 31 deleted 26 of them): declare a conf
    with the code that reads it, or not at all."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    unread = _unread_confs(here)
    assert unread == [], (
        f"{len(unread)} confs are declared and documented but read by "
        f"no code under spark_rapids_tpu/: {unread}")
