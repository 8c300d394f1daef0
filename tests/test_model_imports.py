"""The arrows between the modules of `ray_tpu/models/` point one way: a
module that defines a model family imports no other family's module
(what two families share lives in `models/paged_common.py`, which
imports none of them). The one exception is `llama`, the base model
file, for `rms_norm`. Read off the source with `ast`: an import inside a
function counts as much as one at the top."""

import ast
import pathlib

import pytest

from ray_tpu.models.family import FAMILIES

MODELS = pathlib.Path(__file__).resolve().parents[1] / "ray_tpu" / "models"
FAMILY_MODULES = sorted({module for module, _ in FAMILIES.values()})


def _models_imported(path: pathlib.Path) -> set:
    """Names of the `ray_tpu/models/` modules that `path` imports."""
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom):
            module = node.module or ""
            if node.level == 1 or module.startswith("ray_tpu.models"):
                tail = module.removeprefix("ray_tpu.models").strip(".")
                # `from . import trinity` names modules; `from .trinity
                # import x` names one
                found |= ({tail.split(".")[0]} if tail
                          else {a.name for a in node.names})
        elif isinstance(node, ast.Import):
            found |= {a.name.split(".")[2] for a in node.names
                      if a.name.startswith("ray_tpu.models.")}
    return found


@pytest.mark.parametrize("module", FAMILY_MODULES)
def test_a_family_module_imports_no_other_familys(module):
    others = set(FAMILY_MODULES) - {module, "llama"}
    crossed = _models_imported(MODELS / f"{module}.py") & others
    assert not crossed, (
        f"models/{module}.py imports {sorted(crossed)}: what two families "
        "share belongs in models/paged_common.py")


def test_the_shared_module_imports_no_family():
    crossed = _models_imported(MODELS / "paged_common.py") & set(
        FAMILY_MODULES)
    assert not crossed, crossed


def test_the_walk_sees_every_form_of_import(tmp_path):
    src = tmp_path / "m.py"
    src.write_text(
        "from .trinity import rope\n"
        "from . import phi4flash, cache_row\n"
        "import ray_tpu.models.llama\n"
        "def f():\n"
        "    from ray_tpu.models.deepseek_v3 import swiglu\n"
        "    from ..ops import moe\n")
    assert _models_imported(src) == {
        "trinity", "phi4flash", "cache_row", "llama", "deepseek_v3"}
