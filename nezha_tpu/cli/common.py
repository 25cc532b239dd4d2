"""Shared CLI helpers (nezha-train / nezha-generate / nezha-export)."""

from __future__ import annotations

import sys


def setup_jax(args) -> None:
    """The common jax preamble for every CLI entry: optional platform
    override (must precede backend init), then the same-machine persistent
    compile cache (re-runs of a config skip the 20-40 s TPU first
    compile). One place so the entries cannot drift."""
    import jax

    if getattr(args, "platform", None):
        jax.config.update("jax_platforms", args.platform)
    from nezha_tpu.utils import enable_persistent_compile_cache
    enable_persistent_compile_cache()


def restore_variables_any(ckpt_dir: str, model, optimizer):
    """Model variables from EITHER checkpoint format a `nezha-train` run
    may have written: dense npz (single/dp/sp) or per-shard
    (zero1/gspmd/pp). The sgd-or-whatever template trick: restore walks
    TEMPLATE leaves only, and every optimizer's state carries ``step`` at
    the same path, so a minimal-optimizer template reads any checkpoint.
    Raises SystemExit when neither format is present."""
    import jax

    from nezha_tpu.train import checkpoint as ckpt
    from nezha_tpu.train import sharded_checkpoint as sckpt
    from nezha_tpu.train.loop import init_train_state

    template = init_train_state(model, optimizer, jax.random.PRNGKey(0))
    if _is_graph_layout(ckpt_dir, ckpt):
        # Graph-engine trainers write {"params", ...optimizer slots}
        # (AdamW: mu/nu/step; momentum: vel) with module-layout params.
        # A params-only template restores just what the callers consume —
        # restore ignores npz keys the template doesn't name, so the
        # optimizer slots are never reconstructed.
        p = template["variables"]["params"]
        g_restored, step = ckpt.try_restore(ckpt_dir, {"params": p})
        print(f"restored step {step} (graph-engine layout) from "
              f"{ckpt_dir}", file=sys.stderr)
        return {"params": g_restored["params"], "state": {}}
    restored, step = ckpt.try_restore(ckpt_dir, template)
    if restored is None:
        restored, step = sckpt.try_restore_sharded(ckpt_dir, template)
    if restored is None:
        raise SystemExit(f"no checkpoint (npz or sharded) in {ckpt_dir}")
    print(f"restored step {step} from {ckpt_dir}", file=sys.stderr)
    return restored["variables"]


def _is_graph_layout(ckpt_dir: str, ckpt) -> bool:
    """True when the newest npz checkpoint carries graph-engine keys.

    Reads only the zip directory (``z.files``), not the arrays — layout
    dispatch must not cost a full decompress of a GB-scale checkpoint."""
    import os

    import numpy as np

    step = ckpt.latest_step(ckpt_dir)
    if step is None:
        return False
    path = os.path.join(ckpt_dir, f"step_{step:08d}.npz")
    with np.load(path) as z:
        return not any(k.startswith("variables/") for k in z.files)


def ckpt_has_scan_trunk(ckpt_dir: str) -> bool:
    """True when the newest checkpoint in ``ckpt_dir`` (either format)
    stores trunk params in the scan layout (``h_scan`` for GPT-2,
    ``layers_scan`` for BERT — a ``--scan-layers`` training run). Lets
    nezha-generate/nezha-export rebuild the model with the matching
    layout instead of failing to match ``h0..hN`` template leaves. Reads
    directory listings / zip indexes only, never the arrays."""
    import os
    from pathlib import Path

    import numpy as np

    from nezha_tpu.train import checkpoint as ckpt

    def scan_key(k: str) -> bool:
        return any(f"/{s}/" in k or k.startswith(f"{s}/")
                   for s in ("h_scan", "layers_scan"))

    step = ckpt.latest_step(ckpt_dir)
    if step is not None:
        path = os.path.join(ckpt_dir, f"step_{step:08d}.npz")
        with np.load(path) as z:
            return any(scan_key(k) for k in z.files)
    # Sharded layout: leaf paths live in the meta_p*.json indexes. Use
    # the sharded latest_step (honors COMPLETE markers) so detection
    # looks at the SAME checkpoint restore will read — a torn newer dir
    # must not flip the layout decision.
    from nezha_tpu.train import sharded_checkpoint as sckpt

    sstep = sckpt.latest_step(ckpt_dir)
    if sstep is None:
        return False
    sdir = Path(ckpt_dir) / f"step_{sstep:08d}.sharded"
    for meta in sdir.glob("meta_p*.json"):
        try:
            text = meta.read_text()
        except OSError:
            continue
        # Each meta names every leaf path prefix.
        return "h_scan" in text or "layers_scan" in text
    return False


def gpt2_for_preset(preset: str, *, scan_layers: bool = False):
    """THE preset -> GPT2 model mapping for every inference CLI
    (`nezha-generate`, `nezha-serve`, `nezha-reshard` — one site, so
    the serve/reshard/load paths can never build models with drifting
    configs or numerics): full decodes bf16 (the checkpoint's training
    policy), tiny fp32."""
    from nezha_tpu.models.gpt2 import GPT2, GPT2Config
    from nezha_tpu.tensor import bf16_policy

    if preset == "full":
        return GPT2(GPT2Config(scan_layers=scan_layers),
                    policy=bf16_policy())
    from nezha_tpu.cli.train import TINY_GPT2_KW
    return GPT2(GPT2Config(**TINY_GPT2_KW, scan_layers=scan_layers))


def load_gpt2_for_inference(args):
    """(model, variables) for the inference CLIs (`nezha-generate`,
    `nezha-serve`) from any of their three weight sources: --hf-dir
    (transformers checkpoint), --ckpt-dir (either nezha-train format,
    scan-layers auto-detected and unstacked ONCE to the unrolled decode
    layout), or --random-init. Policies mirror nezha-train's presets:
    full decodes bf16, tiny fp32 — greedy decode must run the same
    compute numerics as the checkpoint's training run."""
    import jax

    from nezha_tpu.models.gpt2 import GPT2

    if getattr(args, "hf_dir", None):
        import transformers

        hf = transformers.GPT2LMHeadModel.from_pretrained(args.hf_dir)
        from nezha_tpu.models.convert import gpt2_from_hf
        return gpt2_from_hf(hf)

    # --scan-layers checkpoints store the trunk under h_scan with a
    # leading layer dim; restore with the matching template, then
    # unstack ONCE to the unrolled layout for decode — the scan model's
    # cache path would otherwise slice every stacked param per decode
    # step (doubling param traffic in the latency-bound loop).
    scan = False
    if getattr(args, "ckpt_dir", None):
        scan = ckpt_has_scan_trunk(args.ckpt_dir)
    model = gpt2_for_preset(args.model_preset, scan_layers=scan)
    if getattr(args, "ckpt_dir", None):
        # Either checkpoint format: dense npz OR the per-shard layout
        # that zero1/gspmd/pp training writes. Generation needs the
        # variables leaf only (optimizer state is ignored); no point
        # materializing a random init just to overwrite it.
        from nezha_tpu import optim
        variables = restore_variables_any(args.ckpt_dir, model,
                                          optim.sgd(0.1))
        if scan:
            import dataclasses as _dc

            from nezha_tpu.models.gpt2 import unstack_layer_params
            variables = {
                "params": unstack_layer_params(
                    variables["params"], model.cfg.num_layers),
                "state": variables.get("state", {})}
            model = GPT2(_dc.replace(model.cfg, scan_layers=False),
                         policy=model.policy)
    else:
        variables = model.init(jax.random.PRNGKey(args.seed))
    return model, variables


# ``nezha-serve --model`` beside gpt2: name -> (module of ``nezha_tpu.models``,
# its builder ``build(preset)``, what ``--help`` says of it). A further
# model is one line here.
RANDOM_INIT_MODELS = {
    "mistral_small4": ("mistral4", "mistral_small4",
                       "Mistral-Small-4: latent attention, dropless experts"),
    "k_exaone": ("exaone_moe", "k_exaone",
                 "K-EXAONE: grouped-query heads, window layers in a ring of "
                 "blocks beside global layers, sigmoid-routed experts"),
    "kimi_linear": ("kimi_linear", "kimi_linear",
                    "Kimi-Linear: linear-attention layers with a recurrent "
                    "state a slot beside latent-attention layers, "
                    "sigmoid-routed experts"),
    "xing4": ("xing4", "xing4",
              "Xing4.0: four residual streams mixed by manifold-constrained "
              "hyper-connections round latent attention and sigmoid-routed "
              "experts"),
    "falcon_h1": ("falcon_h1", "falcon_h1",
                  "Falcon-H1: Mamba-2 heads beside grouped-query attention "
                  "in every layer, a float32 state a slot"),
    "minicpm_sala": ("minicpm_sala", "minicpm_sala",
                     "MiniCPM-SALA: block-sparse attention over the paged "
                     "table with a compressed-key cache beside lightning "
                     "linear-attention layers"),
}
SERVED_MODELS = ("gpt2", *RANDOM_INIT_MODELS)


def load_model_for_inference(args):
    """(model, variables) for ``nezha-serve --model``: GPT-2 from any of
    its three weight sources, or one of ``RANDOM_INIT_MODELS`` with random
    weights (the one source they have: no checkpoint converter exists for
    them)."""
    name = getattr(args, "model", "gpt2")
    if name == "gpt2":
        return load_gpt2_for_inference(args)
    if not getattr(args, "random_init", False):
        raise SystemExit(
            f"--model {name} takes --random-init only (no "
            f"checkpoint or Hugging Face converter exists for it)")
    import importlib

    import jax

    module, builder, _ = RANDOM_INIT_MODELS[name]
    model = getattr(importlib.import_module(f"nezha_tpu.models.{module}"),
                    builder)(args.model_preset)
    # model.init builds the tree leaf by leaf in the policy's parameter
    # dtype (bf16 at the full preset: 2 bytes a parameter on the device).
    return model, model.init(jax.random.PRNGKey(args.seed))


def resolve_eos_id(explicit, tokenizer, vocab: int, flag: str = "--eos-id"):
    """ONE EOS policy for the inference CLIs (generate + serve): an
    explicit flag wins and is validated hard (out-of-vocab = user
    error); otherwise the loaded tokenizer's natural EOS, which quietly
    disables (stderr note) when it falls outside the model vocab — a
    big-vocab tokenizer on a small model must not break decoding that
    worked before EOS support. Negative values force-disable."""
    if explicit is not None and explicit >= vocab:
        raise SystemExit(f"{flag} {explicit} outside the model vocab "
                         f"[0, {vocab})")
    eos_id = explicit
    if eos_id is None and tokenizer is not None:
        from nezha_tpu.data.tokenizer import default_eos_id
        eos_id = default_eos_id(tokenizer)
        if eos_id is not None and eos_id >= vocab:
            print(f"note: tokenizer EOS id {eos_id} is outside this "
                  f"model's vocab [0, {vocab}); EOS stopping disabled",
                  file=sys.stderr)
            eos_id = None
    if eos_id is not None and eos_id < 0:
        eos_id = None
    return eos_id
