"""What the tree owes its one measuring system and its living documents.

(a) Every cell of ``BENCHMARK.json`` and ``benchmarks/candidates.json`` hands
``Config.from_dict`` the ``params`` of its configuration file with its traffic
mix's on top (``benchmarks.harness.Spec.params``): a PR that renames or drops
a ``Config`` field a cell passes fails here, on the CPU, not on the chip.
(b) A living document names only files, tests and ``make`` targets that exist.
(c) No switch of the retired ``bench.py`` is left in code or documents."""

import functools
import os
import re

import pytest

from benchmarks import harness
from tpu_rl.config import Config
from tpu_rl.data.layout import BatchLayout

ROOT = harness.ROOT
DOCUMENTS = (
    "README.md", "docs/ARCHITECTURE.md", "PERF.md", ".claude/skills/verify/SKILL.md",
)
CHECKED_DIRS = ("examples/", "tests/", "tpu_rl/", "tools/", "benchmarks/", "docs/")
PROGRAM_DIRS = ("tpu_rl", "examples", "tools", "benchmarks")


def _read(rel: str) -> str:
    with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
        return f.read()


def _files(*tops: str) -> list[str]:
    """Every file under the given top-level directories, relative to the root."""
    return [
        os.path.relpath(os.path.join(dirpath, f), ROOT)
        for top in tops
        for dirpath, _dirs, files in os.walk(os.path.join(ROOT, top))
        for f in files
    ]


# ----------------------------------------------------------- (a) cell params
def _cells() -> list[dict]:
    """Registered and candidate cells, each with its config file's path."""
    out = []
    for rel in ("BENCHMARK.json", "benchmarks/candidates.json"):
        doc = harness.load_json(os.path.join(ROOT, rel))
        files = {c["name"]: c["file"] for c in doc["configs"]}
        out += [dict(w, file=files[w["config"]]) for w in doc["workloads"]]
    return out


@pytest.mark.parametrize(
    "config_file", sorted(os.listdir(os.path.join(harness.HERE, "configs")))
)
def test_cell_params_build_config_and_layout(config_file):
    rel = f"benchmarks/configs/{config_file}"
    cells = [c for c in _cells() if c["file"] == rel]
    assert cells, f"no cell runs {rel}"
    for cell in cells:
        spec = harness.Spec(
            cell=cell,
            config=harness.load_json(os.path.join(ROOT, rel)),
            traffic=harness.load_json(
                os.path.join(harness.HERE, "traffic", f"{cell['traffic']}.json")
            ),
            seed=0, seconds=0.0, trace=False, t_start=0.0,
        )
        cfg = Config.from_dict(spec.params)
        for name, value in spec.params.items():  # handed over, not defaulted
            got = getattr(cfg, name)
            want = tuple(value) if isinstance(value, list) else value
            assert got == want, (cell["name"], name, got, want)
        layout = BatchLayout.from_config(cfg)
        assert layout.width("obs") > 0, cell["name"]


# ------------------------------------------------------ (b) living documents
_FENCE = re.compile(r"^```.*?^```", re.S | re.M)
_INLINE = re.compile(r"`+([^`\n]+)`+")
_PATTERN_CHARS = set("*[]{}<>$…")
_ROOT_NAME = re.compile(r"[A-Za-z0-9_.\-]+\.(?:py|json|jsonl|md)")
_TARGET = re.compile(r"[a-z][a-z0-9-]*")


def _spans(text: str) -> list[str]:
    """The back-ticked spans of a document, and each line of a fenced block."""
    fenced = _FENCE.findall(text)
    lines = [ln for block in fenced for ln in block.splitlines()[1:-1]]
    return lines + _INLINE.findall(_FENCE.sub("", text))


@functools.cache
def _inventory() -> tuple[set[str], set[str], str]:
    """The Makefile's targets, every file name of the tree, and the program's
    Python sources outside tests/: a bare file name a document gives is either
    a file of the tree or a name the program itself writes or reads."""
    targets = set(re.findall(r"^([a-z][a-z0-9-]*):", _read("Makefile"), re.M))
    names = set(os.listdir(ROOT))
    names.update(
        os.path.basename(f)
        for f in _files(*PROGRAM_DIRS, "tests", "docs", "configs", "native")
    )
    code = "\n".join(
        _read(f)
        for f in ("chip_smoke.py", "__graft_entry__.py", *_files(*PROGRAM_DIRS))
        if f.endswith(".py")
    )
    return targets, names, code


def _missing(doc: str) -> list[str]:
    targets, basenames, code = _inventory()
    missing = []
    for span in _spans(_read(doc)):
        tokens = span.split()
        for i, raw in enumerate(tokens):
            tok = raw.strip(".,;:()'\"")
            if _PATTERN_CHARS & set(tok):
                continue
            if i and tokens[i - 1] == "make" and _TARGET.fullmatch(tok):
                if tok not in targets:
                    missing.append(f"make {tok}")
                continue
            path, _, test = tok.partition("::")
            path = re.sub(r"(:[0-9][0-9,\-]*)+$", "", path).split("#")[0]
            if path.startswith(CHECKED_DIRS):
                if not os.path.exists(os.path.join(ROOT, path)):
                    missing.append(path)
                elif test and test.split("::")[-1].split("[")[0] not in _read(path):
                    missing.append(tok)
            elif _ROOT_NAME.fullmatch(path):
                # run-time outputs (telemetry.json, learn.jsonl, ...) are names
                # the code holds; a committed record is a file of the tree
                if path not in basenames and path not in code:
                    missing.append(path)
    return sorted(set(missing))


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_living_document_names_only_what_exists(doc):
    assert _missing(doc) == []


# ------------------------------------------------------ (c) retired switches
def test_no_retired_bench_switch_left():
    needle = "TPU_RL_" + "BENCH_"
    texts = ["Makefile", "chip_smoke.py", "README.md", "docs/ARCHITECTURE.md"] + [
        f
        for f in _files("tpu_rl", "examples", "tools", "tests")
        if f.endswith((".py", ".md", ".json", ".toml", ".cpp", ".txt"))
    ]
    assert [rel for rel in texts if needle in _read(rel)] == []
