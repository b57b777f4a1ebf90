"""The documents name only files and modules that exist.

A reader is sent to a path or to a ``python -m`` command by ``README.md`` and
``docs/*.md``; a PR that deletes or moves a file must move the documents in
the same change, or this fails. Checked: every path under the repo's top
directories that appears inside backticks or inside a fenced block, every
script a ``python <script>.py`` command runs, and every
``python -m transformer_tpu.<module>`` (``perfbench.<module>`` too)."""

import glob
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
DOCS = ["README.md"] + sorted(
    str(p.relative_to(REPO)) for p in (REPO / "docs").glob("*.md")
)

_TOP = "transformer_tpu|perfbench|tests|benchmarks|docs|examples"
# A path starts at a top directory that is not itself inside a longer path or
# word; it ends where file-name characters end ("::name" and ":123" fall off).
# "{a,b}" names both; a "<placeholder>" stands for any rest of the name.
_PATH = re.compile(rf"(?<![\w./-])(?:{_TOP})/(?:[\w./*-]|\{{[\w.,*-]*\}})*<?")
_BRACES = re.compile(r"\{([^{}]*)\}")
_MODULE = re.compile(r"python3?\s+-m\s+((?:transformer_tpu|perfbench)(?:\.\w+)*)")
_SCRIPT = re.compile(r"python3?\s+([\w./-]+\.py)\b")
_FENCE = re.compile(r"^```.*?$(.*?)^```", re.M | re.S)
_TICKS = re.compile(r"`([^`\n]+)`")


def _code(text: str) -> list[str]:
    fenced = _FENCE.findall(text)
    return fenced + _TICKS.findall(_FENCE.sub("", text))


def _expand(path: str) -> list[str]:
    m = _BRACES.search(path)
    if m is None:
        return [path[:-1] + "*" if path.endswith("<") else path.rstrip(".-")]
    return [
        q for alt in m.group(1).split(",")
        for q in _expand(path[:m.start()] + alt + path[m.end():])
    ]


def _named(text: str) -> tuple[set, set]:
    paths, modules = set(), set()
    for chunk in _code(text):
        for m in _PATH.finditer(chunk):
            paths.update(_expand(m.group(0)))
        paths.update(_SCRIPT.findall(chunk))
        modules.update(_MODULE.findall(chunk))
    return paths, modules


def _module_exists(name: str) -> bool:
    base = REPO.joinpath(*name.split("."))
    return base.with_suffix(".py").is_file() or (base / "__init__.py").is_file()


def test_extraction_sees_paths_and_modules():
    paths, modules = _named(
        "see `tests/test_docs.py::test_x`, `tests/test_{docs,obs}.py.`, "
        "`perfbench/workloads/<cell>.json` and `serve/scheduler.py`\n"
        "```bash\npython -m transformer_tpu.obs summarize docs/*.md\n"
        "python chip_smoke.py --rehearse\n```\n"
        "and /root/repo/tests/nothing.py outside code\n"
    )
    assert paths == {
        "tests/test_docs.py", "tests/test_obs.py", "perfbench/workloads/*",
        "docs/*.md", "chip_smoke.py",
    }
    assert modules == {"transformer_tpu.obs"}


@pytest.mark.parametrize("doc", DOCS)
def test_paths_named_in_docs_exist(doc):
    paths, modules = _named((REPO / doc).read_text(encoding="utf-8"))
    assert paths or modules, f"{doc} names nothing: the extraction is broken"
    missing = sorted(
        p for p in paths
        if not (glob.glob(str(REPO / p)) if "*" in p else (REPO / p).exists())
    )
    missing += sorted(m for m in modules if not _module_exists(m))
    assert not missing, f"{doc} names what does not exist: {missing}"
