"""What holds of every catalog family's file in ``tpu_rl/models/`` at once: the
parameter tree and the acting carry each builds at its test module's tiny
widths, held to what the commit before the shared layers and the trunk left
granite's and nemotron's files (4aa4e3c) built; and the direction of the
package's imports — a family's module reads ``backbone.py``, ``layers.py`` and
``mamba2.py`` and never another family's, and those three read no family's."""

import ast
import hashlib
import pathlib

import jax
import pytest

from test_glm4_moe_lite import family_params
from tpu_rl.config import ARCH_CHECKS, Config
from tpu_rl.models.families import build_family

MODELS = pathlib.Path(__file__).resolve().parent.parent / "tpu_rl" / "models"
SHARED = ("backbone", "layers", "mamba2")

# sha256 of the sorted ``path : shape : dtype`` lines of ``family.init_params``
# (from shapes: no weight is made) and, last, ``family.carry_widths``, recorded
# on 4aa4e3c. ``benchmarks/reference/*.py`` read the system's tree by these
# paths and checkpoints are keyed by them: a PR that changes one on purpose
# records the new digest here and says so.
TREES = {
    "granite_hybrid": "1916488bd0fe59988488c96e93a94ec5b29d199ef7c9de8a10f5d957a82b8599",
    "nemotron_h": "59f65c7cf6f3ffd6f75421415676465618fea53d79d26c4bce883d3dd59c3805",
    "smallthinker": "26703d59ccd3c3fc76256cc5e7f0e27ef81ee9502f79a0ab68c7090ad295e45e",
    "qwen3_next": "8cb5c7de1180e1383abd2cc6c9c86273b158796ef51a05ad1f2c6fa4161c6da3",
    "glm4_moe_lite": "d40ba6cb6c1a42cef0f74c09ce8a7ebbef547a4c0512a3fb09c243032ce1d16d",
    # PR 43: the family's own, as the PR that brought it built it
    "lfm2_moe": "4be6e9e331cc53d1dba893b33df63e32f20a09ce00a80b923ff6dafd57a85513",
    # PR 46: the family's own, as the PR that brought it built it
    "evabyte": "be06c64d016d901adbae8feef1bfe3f2a3ec39fb9b31b66b142ea45c6d0c741a",
    # PR 51: the family's own, as the PR that brought it built it
    "ling_flash": "0cc9d71f113ea7ce2ce6e39ce55edb8a042ec28d8ba131679013286320ef1093",
}


def test_every_catalog_family_is_pinned():
    assert set(TREES) == set(ARCH_CHECKS)


@pytest.mark.parametrize("model", TREES)
def test_the_parameter_tree_and_the_carry_are_as_before(model):
    family = build_family(Config.from_dict(family_params(model)))
    tree = jax.eval_shape(family.init_params, jax.random.key(0))
    lines = sorted(
        f"{jax.tree_util.keystr(path)} : {leaf.shape} : {leaf.dtype}"
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree))
    lines.append(f"carry_widths : {family.carry_widths}")
    digest = hashlib.sha256("\n".join(lines).encode()).hexdigest()
    assert digest == TREES[model], f"{model} now builds {digest}:\n" + "\n".join(lines)


def imported_models(path: pathlib.Path) -> set[str]:
    """The modules of ``tpu_rl.models`` that the file at ``path`` imports,
    wherever in it the statement stands."""
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module == "tpu_rl.models":
                found.update(alias.name for alias in node.names)
            elif node.module.startswith("tpu_rl.models."):
                found.add(node.module.split(".")[2])
        elif isinstance(node, ast.Import):
            found.update(
                alias.name.split(".")[2] for alias in node.names
                if alias.name.startswith("tpu_rl.models."))
    return found


def test_no_family_imports_another_and_the_shared_modules_import_none():
    families = set(ARCH_CHECKS)
    for name in families:
        others = imported_models(MODELS / f"{name}.py") & (families - {name})
        assert not others, f"models/{name}.py imports {sorted(others)}"
    for name in SHARED:
        taken = imported_models(MODELS / f"{name}.py") & families
        assert not taken, f"models/{name}.py imports {sorted(taken)}"
