"""`models/decoder_stack.py` sits under the seven decoder modules: which way the
imports in `models/` point, read from the source by `ast`, and the run
machinery its three users share.  That the move changed no cell's program is
tests/test_cell_steps.py."""

import ast
from pathlib import Path

from deeplearning_cfn_tpu.models import conv_attn_moe, decoder_stack, ssm_attn_moe, window_attn_moe
from deeplearning_cfn_tpu.models.window_attn_moe import WindowAttnMoeConfig

MODELS = Path(decoder_stack.__file__).parent
PACKAGE = "deeplearning_cfn_tpu"
KINDS = ("mla_moe", "conv_attn_moe", "window_attn_moe", "ssm_attn_moe", "looped_decoder", "mamba_attn")


def _imports(path: Path):
    """(module, name) of every name the file imports from the package, at any
    depth: `from a.b import c` is (a.b, c), and so is `import a.b.c`."""
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom) and (node.module or "").startswith(PACKAGE):
            assert node.level == 0
            yield from ((node.module, alias.name) for alias in node.names)
        elif isinstance(node, ast.Import):
            yield from (
                tuple(alias.name.rsplit(".", 1)) for alias in node.names if alias.name.startswith(PACKAGE + ".")
            )


def test_the_imports_in_models_point_one_way():
    imports = {path.stem: sorted(_imports(path)) for path in sorted(MODELS.glob("*.py"))}
    assert set(KINDS) | {"decoder_stack", "llama"} <= set(imports)
    # no private name crosses a module's edge
    private = [(stem, m, n) for stem, found in imports.items() for m, n in found if n.startswith("_")]
    assert private == []
    # `decoder_stack` is under `models/`, not in it
    in_models = lambda m, n: m.startswith(f"{PACKAGE}.models") or (m, n) == (PACKAGE, "models")
    assert [(m, n) for m, n in imports["decoder_stack"] if in_models(m, n)] == []
    # no kind imports another kind, as a module or for a name of it
    for kind in KINDS:
        others = [
            (m, n) for m, n in imports[kind]
            if in_models(m, n) and (m.rsplit(".", 1)[-1] in KINDS or n in KINDS)
        ]
        assert others == [], kind
    # the trainer is reached from one place (`llama_memory` prices a `TrainerConfig`: another name)
    assert [stem for stem, found in imports.items() if (f"{PACKAGE}.train.trainer", "Trainer") in found] == [
        "decoder_stack"
    ]


def test_the_decoders_of_runs_share_the_run_machinery():
    """One definition of a run, of its stacked weights and of the scan over
    runs: `models/decoder_stack.py`'s functions, called by all three modules
    (the pattern string's units are `ssm_attn_moe.units_of`'s, not `runs_of`'s)."""
    for module in (conv_attn_moe, window_attn_moe, ssm_attn_moe):
        for name in ("init_runs", "run_specs", "scan_runs"):
            assert getattr(module, name) is getattr(decoder_stack, name), (module.__name__, name)
    assert conv_attn_moe.runs_of is window_attn_moe.runs_of is decoder_stack.runs_of
    kinds = (("a", 1), ("a", 1), ("b", 2), ("a", 1))
    assert decoder_stack.runs_of(kinds) == ((("a", 1), 2), (("b", 2), 1), (("a", 1), 1))
    assert WindowAttnMoeConfig.tiny().runs == decoder_stack.runs_of(WindowAttnMoeConfig.tiny().kinds)
