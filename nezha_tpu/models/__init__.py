"""Model zoo — the five benchmark workloads of BASELINE.json (SURVEY.md §2):
MLP, ResNet-50, Wide-ResNet-101, GPT-2 124M, BERT-base."""

from nezha_tpu.models.mlp import MLP

__all__ = ["MLP"]


_LAZY = {
    "ResNet": "resnet", "resnet50": "resnet", "wide_resnet101": "resnet",
    "GPT2": "gpt2", "GPT2Config": "gpt2", "gpt2_124m": "gpt2",
    "Bert": "bert", "BertConfig": "bert", "bert_base": "bert",
    "Mistral4": "mistral4", "Mistral4Config": "mistral4",
    "mistral_small4": "mistral4",
    "ExaoneMoe": "exaone_moe", "ExaoneMoeConfig": "exaone_moe",
    "k_exaone": "exaone_moe",
    "KimiLinear": "kimi_linear", "KimiLinearConfig": "kimi_linear",
    "kimi_linear": "kimi_linear",
    "Xing4": "xing4", "Xing4Config": "xing4", "xing4": "xing4",
    "FalconH1": "falcon_h1", "FalconH1Config": "falcon_h1",
    "falcon_h1": "falcon_h1",
    "MiniCPMSALA": "minicpm_sala", "MiniCPMSALAConfig": "minicpm_sala",
    "minicpm_sala": "minicpm_sala",
    "generate": "generate", "init_cache": "generate",
    "gpt2_from_hf": "convert", "bert_from_hf": "convert",
    "gpt2_params_from_hf": "convert", "gpt2_params_to_hf": "convert",
    "bert_params_from_hf": "convert",
}


def __getattr__(name):
    # Lazy imports keep `import nezha_tpu` fast; heavy models load on demand.
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(f"nezha_tpu.models.{_LAZY[name]}")
        return getattr(mod, name)
    raise AttributeError(name)
