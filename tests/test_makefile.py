"""The Makefile's targets run UNATTENDED (CI, the `*-smoke` chains): a
recipe that names a module, script or test file that is gone fails only
when somebody finally runs it. One case per target pins every
`cake_tpu.tools.<name>`, `-m cake_tpu.<module>`, `*.py` script and
`tests/<file>` its recipe names, and its prerequisites, to what the
checkout holds. The measuring stack is `benchmark/run.py` (its command is
in `BENCHMARK.json`); no recipe drives another one."""

import re
from pathlib import Path

import pytest

_ROOT = Path(__file__).resolve().parent.parent
_MAKEFILE = (_ROOT / "Makefile").read_text()
_RULE = re.compile(r"^([A-Za-z0-9_./-]+)\s*:(?!=)\s*(.*)$")


def _targets() -> dict:
    """target -> (prerequisites, recipe text with continuations joined)"""
    out, name = {}, None
    for line in _MAKEFILE.replace("\\\n", " ").splitlines():
        if line.startswith("\t"):
            if name is not None:
                out[name][1].append(line.strip())
            continue
        m = _RULE.match(line)
        name = None
        if m and m.group(1) != ".PHONY":
            name = m.group(1)
            out[name] = (m.group(2).split(), [])
    return out


_TARGETS = _targets()


def _module_exists(dotted: str) -> bool:
    base = _ROOT.joinpath(*dotted.split("."))
    return (base.with_suffix(".py").exists()
            or (base / "__main__.py").exists())


@pytest.mark.parametrize("target", sorted(_TARGETS))
def test_target_names_only_what_exists(target):
    prereqs, recipe = _TARGETS[target]
    assert recipe or prereqs, f"`{target}` has neither recipe nor prerequisite"
    for pre in prereqs:
        assert pre in _TARGETS or (_ROOT / pre).exists(), (
            f"`{target}` depends on {pre}: neither a target nor a file")
    text = "\n".join(recipe)
    for module in re.findall(r"-m\s+(cake_tpu(?:\.[a-z0-9_]+)+)", text):
        assert _module_exists(module), (
            f"`{target}` runs -m {module}, which does not exist")
    for path in re.findall(r"(?<![\w./-])((?:[\w-]+/)*[\w-]+\.py)\b", text):
        assert (_ROOT / path).exists(), (
            f"`{target}` names {path}, which does not exist")
    for path in re.findall(r"(?<![\w./-])(tests/[\w./-]*)", text):
        assert (_ROOT / path).exists(), (
            f"`{target}` names {path}, which does not exist")


def test_the_extractor_sees_the_makefile():
    assert len(_TARGETS) >= 25 and "perf-smoke" in _TARGETS
    tools = re.findall(r"cake_tpu\.tools\.([a-z0-9_]+)",
                       "\n".join(r for _, rs in _TARGETS.values() for r in rs))
    assert tools, "Makefile no longer invokes any cake_tpu.tools module?"
    phony = re.search(r"^\.PHONY:(.*)$", _MAKEFILE, re.M).group(1).split()
    assert set(phony) <= set(_TARGETS), sorted(set(phony) - set(_TARGETS))


def test_no_recipe_sets_a_bench_knob():
    """`CAKE_BENCH_*` was the knob surface of the measuring script that
    `benchmark/` replaced: a recipe that sets one measures nothing."""
    assert not re.findall(r"CAKE_BENCH_[A-Z0-9_]+", _MAKEFILE)


def test_no_scratch_queue_scripts_return():
    """The wait-then-measure scratch scripts went long ago; what is
    measured on the chip goes through `benchmark/run.py`."""
    assert sorted(_ROOT.glob("tools_bench_queue*.sh")) == []
