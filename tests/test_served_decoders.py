"""What a served decoder is made of. The six served decoders share one
shell (``models/decoder.py``) and one set of attention mixers
(``models/attention.py``) and import no other model module: an edit to
one model's file cannot break another's."""

import ast
import os

import pytest

_MODELS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "nezha_tpu", "models")
SERVED = ("mistral4", "exaone_moe", "kimi_linear", "xing4", "falcon_h1",
          "minicpm_sala")
SHARED = {"decoder", "attention"}


def _model_modules_imported(path: str) -> set:
    """The ``nezha_tpu.models`` modules a source file imports, in any
    import form, at any depth (function-local imports too)."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module == "nezha_tpu.models":
                found.update(a.name for a in node.names)
            elif node.module.startswith("nezha_tpu.models."):
                found.add(node.module.split(".")[2])
        elif isinstance(node, ast.Import):
            found.update(a.name.split(".")[2] for a in node.names
                         if a.name.startswith("nezha_tpu.models."))
    return found


@pytest.mark.parametrize("name", SERVED)
def test_a_served_decoder_imports_no_sibling(name):
    imported = _model_modules_imported(os.path.join(_MODELS, f"{name}.py"))
    assert imported <= SHARED, (
        f"models/{name}.py imports {sorted(imported - SHARED)}: what the "
        f"served decoders share lives in models/decoder.py or "
        f"models/attention.py")
