"""`nezha-train`: run any of the five benchmark configs end-to-end.

    python -m nezha_tpu.cli.train --config mlp_mnist --steps 200
    python -m nezha_tpu.cli.train --config resnet50_imagenet --mesh dp=8 \
        --batch-size 256 --steps 50 --platform cpu

Configs mirror BASELINE.json (SURVEY.md §0): mlp_mnist (single-process),
resnet50_imagenet (DP all-reduce), gpt2_124m (bf16 GEMM), bert_base_zero1
(ZeRO-1 reduce-scatter/all-gather), wrn101_large_batch (mixed bf16/fp32).
"""

from __future__ import annotations

import argparse
import itertools
import json
import re
import sys
from typing import Any, Callable, Dict, Iterator, Optional

import numpy as np


def _parse_mesh(spec: Optional[str]) -> Optional[Dict[str, int]]:
    if not spec:
        return None
    axes = {}
    for part in spec.split(","):
        name, _, size = part.partition("=")
        axes[name.strip()] = int(size)
    return axes


class Config:
    def __init__(self, build_model: Callable, loss_fn: Callable,
                 batches: Callable[[int], Iterator[dict]],
                 build_optimizer: Callable, default_batch: int,
                 parallel_mode: str = "dp",
                 eval_batches: Optional[Callable] = None,
                 eval_stat: Optional[Callable] = None,
                 tiny: Optional[Dict[str, Callable]] = None,
                 tp_rules=None, pipeline_spec: Optional[Callable] = None,
                 sp_model: Optional[Callable] = None,
                 graph_opt: Optional[Dict[str, Any]] = None):
        self.build_model = build_model
        self.loss_fn = loss_fn
        self.batches = batches
        self.build_optimizer = build_optimizer
        self.default_batch = default_batch
        self.parallel_mode = parallel_mode  # default --parallel for the config
        self.eval_batches = eval_batches  # bs -> finite iterator, or None
        self.eval_stat = eval_stat        # stat fn for train.eval.evaluate
        # --model-preset tiny: field overrides (build_model/batches/...)
        # producing a seconds-scale variant for CLI mechanics tests.
        self.tiny = tiny
        # Advanced parallelism hooks (None = the mode is unsupported here):
        self.tp_rules = tp_rules            # gspmd Megatron rule table
        self.pipeline_spec = pipeline_spec  # model -> PipelineSpec
        self.sp_model = sp_model            # attn_impl -> Module (seq-par)
        # Graph-engine optimizer pieces ({"schedule": steps -> sched,
        # "weight_decay": float}) — shared with build_optimizer so the two
        # engines can't drift apart.
        self.graph_opt = graph_opt


# The tiny GPT-2 preset's hyperparameters — one definition shared by the
# train and generate CLIs so a `--model-preset tiny` checkpoint always
# round-trips (fp32 DEFAULT_POLICY, for tight mode-vs-mode tolerances).
TINY_GPT2_KW = dict(vocab_size=512, max_positions=96, num_layers=4,
                    num_heads=4, hidden_size=64)
TINY_BERT_KW = dict(vocab_size=512, max_positions=96, num_layers=2,
                    num_heads=4, hidden_size=64)


def _configs() -> Dict[str, Config]:
    # Imports deferred so `--help` stays instant.
    from nezha_tpu import data, models, ops, optim
    from nezha_tpu.models import bert as bert_mod
    from nezha_tpu.models import gpt2 as gpt2_mod
    from nezha_tpu.tensor import bf16_policy
    from nezha_tpu.train import eval as eval_mod

    from nezha_tpu.parallel import BERT_TP_RULES, GPT2_TP_RULES
    from nezha_tpu.parallel import pipeline as pp_mod

    ce = lambda logits, b: ops.softmax_cross_entropy_with_integer_labels(
        logits, b["label"])

    # Tiny presets run the same code paths at seconds scale (fp32 for the
    # transformers so mode-vs-mode numerics tests have tight tolerances).
    def tiny_gpt2(**overrides):
        kw = dict(TINY_GPT2_KW)
        kw.update(overrides)
        return models.GPT2(models.GPT2Config(**kw))

    def tiny_bert(**overrides):
        kw = dict(TINY_BERT_KW)
        kw.update(overrides)
        return models.Bert(bert_mod.BertConfig(**kw))

    tiny_tokens = lambda bs, seq_len=64, **kw: data.synthetic_token_batches(
        bs, seq_len=seq_len, vocab_size=512, **kw)
    tiny_images = lambda bs: data.synthetic_image_batches(
        bs, image_size=32, num_classes=100)

    # One schedule factory per config for BOTH engines (module adamw +
    # graph AdamW-update programs) — tuning it here tunes them together.
    gpt2_sched = lambda steps: optim.warmup_cosine_schedule(
        6e-4, 100, max(steps, 200))
    bert_sched = lambda steps: optim.warmup_cosine_schedule(
        1e-4, 100, max(steps, 200))

    return {
        "mlp_mnist": Config(
            build_model=lambda: models.MLP(),
            loss_fn=ce,
            batches=lambda bs: data.mnist_batches(bs),
            build_optimizer=lambda steps: optim.momentum(0.1),
            default_batch=128,
            parallel_mode="single",
            eval_batches=lambda bs: data.mnist_batches(bs, split="test",
                                                       epochs=1),
            eval_stat=eval_mod.accuracy,
            tiny={}),  # already seconds-scale
        "resnet50_imagenet": Config(
            build_model=lambda **ov: models.resnet50(
                stem="s2d", policy=bf16_policy(), **ov),
            loss_fn=ce,
            batches=lambda bs: data.synthetic_image_batches(bs),
            build_optimizer=lambda steps: optim.momentum(
                optim.warmup_cosine_schedule(0.4, 5 * 312, max(steps, 10)),
                beta=0.9, weight_decay=1e-4),
            default_batch=256,
            parallel_mode="dp",
            tiny={"build_model": lambda **ov: models.ResNet(
                      (1, 1), num_classes=100, policy=bf16_policy(), **ov),
                  "batches": tiny_images}),
        "gpt2_124m": Config(
            # fused_loss_chunk=-1: CE never materializes fp32 [B,S,V]
            # logits (see GPT2Config) — the training-CLI default.
            build_model=lambda **ov: models.gpt2_124m(fused_loss_chunk=-1,
                                                      **ov),
            loss_fn=gpt2_mod.lm_loss,
            batches=lambda bs, seq_len=1024: data.synthetic_token_batches(
                bs, seq_len=seq_len),
            build_optimizer=lambda steps, **kw: optim.adamw(
                gpt2_sched(steps), weight_decay=0.1, **kw),
            default_batch=8,
            parallel_mode="dp",
            eval_batches=lambda bs, seq_len=1024: itertools.islice(
                data.synthetic_token_batches(bs, seq_len=seq_len, seed=1),
                8),
            eval_stat=eval_mod.lm_token_stats,
            tiny={"build_model": tiny_gpt2,
                  "batches": tiny_tokens,
                  "eval_batches": lambda bs, seq_len=64: itertools.islice(
                      tiny_tokens(bs, seed=1, seq_len=seq_len), 4),
                  "sp_model": lambda impl, **ov: tiny_gpt2(
                      attn_impl=impl, fused_loss_chunk=-1, **ov)},
            tp_rules=GPT2_TP_RULES,
            pipeline_spec=pp_mod.gpt2_pipeline_spec,
            sp_model=lambda impl, **ov: models.gpt2_124m(
                attn_impl=impl, fused_loss_chunk=-1, **ov),
            graph_opt={"schedule": gpt2_sched, "weight_decay": 0.1}),
        "bert_base_zero1": Config(
            # fused_loss_chunk=-1: bf16 MLM logits with the fp32 upcast
            # fused into logsumexp (same default as gpt2_124m's head).
            build_model=lambda **ov: models.bert_base(fused_loss_chunk=-1,
                                                      **ov),
            loss_fn=bert_mod.mlm_loss,
            batches=lambda bs: data.synthetic_mlm_batches(bs, seq_len=512),
            build_optimizer=lambda steps, **kw: optim.adamw(
                bert_sched(steps), weight_decay=0.01, **kw),
            default_batch=16,
            parallel_mode="zero1",
            eval_batches=lambda bs: itertools.islice(
                data.synthetic_mlm_batches(bs, seq_len=512, seed=1), 8),
            eval_stat=eval_mod.mlm_token_stats,
            tiny={"build_model": tiny_bert,
                  "batches": lambda bs: data.synthetic_mlm_batches(
                      bs, seq_len=64, vocab_size=512, mask_token=1),
                  "eval_batches": lambda bs: itertools.islice(
                      data.synthetic_mlm_batches(bs, seq_len=64,
                                                 vocab_size=512,
                                                 mask_token=1, seed=1), 4)},
            tp_rules=BERT_TP_RULES,
            graph_opt={"schedule": bert_sched, "weight_decay": 0.01}),
        "wrn101_large_batch": Config(
            build_model=lambda **ov: models.wide_resnet101(
                stem="s2d", policy=bf16_policy(), **ov),
            loss_fn=ce,
            batches=lambda bs: data.synthetic_image_batches(bs),
            build_optimizer=lambda steps: optim.momentum(
                optim.warmup_cosine_schedule(1.6, 500, max(steps, 1000)),
                beta=0.9, weight_decay=1e-4),
            default_batch=512,
            parallel_mode="dp",
            tiny={"build_model": lambda **ov: models.ResNet(
                      (1, 1), num_classes=100, width_factor=2,
                      policy=bf16_policy(), **ov),
                  "batches": tiny_images}),
    }


def _join_world(args):
    """Multi-process launch: dial the coordinator before touching devices
    (SURVEY.md §3 call stack 1 — the reference dialed its gRPC coordinator
    for rank/world rendezvous, then initialized the device runtime).
    Returns (group, coordinator) — either may be None."""
    if not args.coordinator:
        return None, None
    from nezha_tpu import dist
    from nezha_tpu.utils import get_logger, set_rank

    host, _, port = args.coordinator.rpartition(":")
    coord = None
    if args.serve_coordinator:
        coord = dist.Coordinator(world_size=args.world_size, port=int(port))
    group = dist.join(host or "127.0.0.1", int(port),
                      rank_hint=args.rank_hint)
    set_rank(group.rank)
    get_logger("nezha_tpu.cli").info(
        "joined world: rank %d / %d", group.rank, group.world_size)
    if group.world_size > 1 and not args.no_jax_distributed:
        # Rank 0 advertises the jax.distributed address; all ranks enter.
        dist.initialize_jax_distributed(group)
    return group, coord


_IMAGE_CONFIGS = ("resnet50_imagenet", "wrn101_large_batch")


def _nzr_count(path) -> int:
    """Record count from an NZR1 header (magic + int32 n,h,w,c)."""
    with open(path, "rb") as f:
        header = f.read(8)
    return int(np.frombuffer(header[4:8], np.int32)[0])


def _slice_rows(it: Iterator[dict], rank: int, local: int) -> Iterator[dict]:
    """Rows [rank*local, (rank+1)*local) of each globally-identical batch —
    turns a same-seed synthetic stream into per-host-distinct local rows."""
    for b in it:
        yield {k: v[rank * local:(rank + 1) * local] for k, v in b.items()}


def _data_source(args, cfg, batch_size: int, group=None):
    """Training batches: real records via the native C++ loaders when
    ``--data-dir`` holds them (SURVEY.md §2 data loaders), synthetic
    fallback otherwise. Returns (iterator, closer).

    With ``group`` set (multi-process dp/zero1), ``batch_size`` is the
    GLOBAL batch and each host yields only its batch_size/world local rows:
    record loaders read a disjoint shard of each epoch (same-seed shuffle,
    batches ``b % world == rank``, zero coordination traffic), token
    loaders draw a decorrelated window stream, and synthetic streams are
    row-sliced out of the same-seed global batch. The per-mode ``shard``
    fn then assembles the global array from process-local rows
    (``parallel.shard_batch_process_local``)."""
    import os

    world = group.world_size if group is not None else 1
    rank = group.rank if group is not None else 0
    local = batch_size // world
    shard = {"shard_index": rank, "shard_count": world} if world > 1 else {}
    if args.data_dir:
        from nezha_tpu.data.native import ImageRecordLoader, TokenLoader
        if args.config in _IMAGE_CONFIGS:
            rec = os.path.join(args.data_dir, "train.nzr")
            if os.path.exists(rec):
                loader = ImageRecordLoader(rec, local, crop=args.crop,
                                           seed=args.seed, train_augment=True,
                                           **shard)
                print(f"data: {loader.num_examples} image records from {rec}"
                      + (f" (shard {rank}/{world})" if shard else ""),
                      file=sys.stderr)
                return iter(loader), loader.close
        elif args.config == "gpt2_124m":
            for name, dtype in (("train.tokens.u16", np.uint16),
                                ("train.tokens.i32", np.int32)):
                tok = os.path.join(args.data_dir, name)
                if os.path.exists(tok):
                    # Loud range check (mirrors the MLM path): ids at or
                    # beyond the model vocab NaN the CE via out-of-range
                    # target gathers — with no diagnostic at all. Sample
                    # the stream and refuse up front.
                    vocab = cfg.build_model().cfg.vocab_size
                    sample = np.fromfile(tok, dtype=dtype, count=65536)
                    if sample.size and int(sample.max()) >= vocab:
                        raise SystemExit(
                            f"{tok} holds token ids up to "
                            f"{int(sample.max())} but the model vocab is "
                            f"{vocab}; re-pack with a matching tokenizer "
                            f"(nezha-pack-text --tokenizer/--learn-bpe) "
                            f"or train the full-vocab preset")
                    loader = TokenLoader(tok, seq_len=args.seq_len or 1024,
                                         batch_size=local, dtype=dtype,
                                         seed=args.seed, **shard)
                    print(f"data: {loader.num_tokens} tokens from {tok}"
                          + (f" (shard {rank}/{world})" if shard else ""),
                          file=sys.stderr)
                    return iter(loader), loader.close
        elif args.config == "bert_base_zero1":
            # MLM pretraining on the same packed-token format as GPT-2:
            # random [B, S] windows + dynamic masking per batch
            # (data/mlm.py; 80/10/10 recipe, labels -100 off-prediction).
            from nezha_tpu.data.mlm import mlm_batches_from_tokens
            # Geometry comes from the ACTUAL model config (module
            # construction is paramless and cheap), so preset/default
            # edits can't drift the data path out from under the model.
            mcfg = cfg.build_model().cfg
            seq, vocab = mcfg.max_positions, mcfg.vocab_size
            for name, dtype in (("train.tokens.u16", np.uint16),
                                ("train.tokens.i32", np.int32)):
                tok = os.path.join(args.data_dir, name)
                if os.path.exists(tok):
                    mask_token = _resolve_mlm_mask_token(
                        args, mcfg, tok,
                        np.fromfile(tok, dtype=dtype, count=32768))
                    loader = TokenLoader(tok, seq_len=seq, batch_size=local,
                                         dtype=dtype, seed=args.seed,
                                         **shard)
                    print(f"data: {loader.num_tokens} tokens from {tok} "
                          f"(dynamic MLM masking, mask_token="
                          f"{mask_token})"
                          + (f" (shard {rank}/{world})" if shard else ""),
                          file=sys.stderr)
                    it = mlm_batches_from_tokens(
                        iter(loader), vocab_size=vocab,
                        mask_token=mask_token, seed=args.seed,
                        drop_last_column=True)
                    return it, loader.close
        elif args.config == "mlp_mnist":
            os.environ.setdefault("NEZHA_DATA_DIR", args.data_dir)
            if os.path.isdir(os.path.join(args.data_dir, "mnist")):
                print(f"data: MNIST IDX files from {args.data_dir}/mnist",
                      file=sys.stderr)
                it = cfg.batches(batch_size)
                return (_slice_rows(it, rank, local) if world > 1 else it,
                        None)
        print(f"data: no records for {args.config} in {args.data_dir}; "
              f"using synthetic data", file=sys.stderr)
    it = cfg.batches(batch_size)
    return (_slice_rows(it, rank, local) if world > 1 else it), None


def _mask_token_from_corpus_sidecar(tok_path: str) -> Optional[int]:
    """The packed corpus's OWN [MASK] id, when discoverable: the
    ``<tokens>.meta.json`` sidecar nezha-pack-text writes (carries the
    packing tokenizer's mask id), else a ``vocab.txt`` sitting next to the
    tokens file (the `--save-tokenizer <data-dir>` layout). None when
    neither exists."""
    import os

    meta_path = tok_path + ".meta.json"
    if os.path.isfile(meta_path):
        try:
            with open(meta_path, encoding="utf-8") as f:
                meta = json.load(f)
        except (OSError, ValueError):
            meta = {}
        if meta.get("mask_token_id") is not None:
            return int(meta["mask_token_id"])
    vocab_txt = os.path.join(os.path.dirname(os.path.abspath(tok_path)),
                             "vocab.txt")
    if os.path.isfile(vocab_txt):
        with open(vocab_txt, encoding="utf-8") as f:
            for i, line in enumerate(f):
                if line.rstrip("\n") == "[MASK]":
                    return i
    return None


def _resolve_mlm_mask_token(args, mcfg, tok_path: str, sample_ids) -> int:
    """MLM mask id for a packed-token file: the explicit flag; else the
    corpus's own tokenizer metadata (pack-text meta sidecar or an adjacent
    vocab.txt — a --learn-wordpiece vocab puts [MASK] at id 4, where the
    103 convention would silently collide with a real subword, ADVICE r5);
    else the BERT-wordpiece default 103 — refused when the corpus looks
    byte-packed (every sampled id < 256), where 103 is a REAL byte value
    and genuine 0x67 tokens would be indistinguishable from [MASK]
    (ADVICE r4). ONE resolution shared by the train and held-out-eval
    paths."""
    import numpy as np

    if args.mlm_mask_token is not None:
        return args.mlm_mask_token
    resolved = _mask_token_from_corpus_sidecar(tok_path)
    if resolved is not None:
        if resolved >= mcfg.vocab_size:
            raise SystemExit(
                f"{tok_path}: the corpus tokenizer's [MASK] id {resolved} "
                f"is outside the model vocab ({mcfg.vocab_size}); the "
                f"corpus and model vocabularies do not match")
        print(f"mlm: [MASK] id {resolved} resolved from the corpus "
              f"tokenizer metadata next to {tok_path}", file=sys.stderr)
        return resolved
    mask_token = min(103, mcfg.vocab_size - 1)
    sample = np.asarray(sample_ids).ravel()
    if sample.size and int(sample.max()) < 256:
        raise SystemExit(
            f"{tok_path} looks byte-packed (sampled ids all < 256), so "
            f"the default mask_token {mask_token} is a real byte value; "
            f"pass an explicit --mlm-mask-token (>= 256 reserves an id "
            f"byte data cannot produce) or use a WordPiece-tokenized "
            f"corpus")
    return mask_token


def _eval_source(args, cfg, batch_size: int):
    """Eval batches: val.nzr records (deterministic center crop) for the
    CNN configs when present, else the config's built-in eval split.
    Returns (iterator, closer, stat_fn) — iterator None means no eval."""
    import os

    from nezha_tpu.train import eval as eval_mod

    if args.data_dir and args.config in _IMAGE_CONFIGS:
        rec = os.path.join(args.data_dir, "val.nzr")
        if os.path.exists(rec):
            from nezha_tpu.data.native import ImageRecordLoader
            # Largest batch <= requested that divides the record count:
            # the loader emits only full batches per epoch, so any other
            # choice silently drops the tail and biases the accuracy (and
            # a batch > n would be rejected outright).
            n = _nzr_count(rec)
            bs = max(d for d in range(1, min(batch_size, n) + 1)
                     if n % d == 0)
            if bs != batch_size:
                print(f"eval: batch {batch_size} -> {bs} to cover all "
                      f"{n} val records exactly", file=sys.stderr)
            loader = ImageRecordLoader(rec, bs, crop=args.crop,
                                       train_augment=False, epochs=1)
            print(f"eval: {n} val records from {rec}", file=sys.stderr)
            return iter(loader), loader.close, eval_mod.accuracy
    if args.data_dir and args.config in ("gpt2_124m", "bert_base_zero1"):
        import numpy as np
        for name, dtype in (("val.tokens.u16", np.uint16),
                            ("val.tokens.i32", np.int32)):
            tok = os.path.join(args.data_dir, name)
            if os.path.exists(tok):
                # Held-out LM eval: deterministic SEQUENTIAL [B, S+1]
                # windows over the whole file, one epoch — exhaustive and
                # reproducible, unlike the training loader's sampled
                # windows. Geometry mirrors the train path.
                mcfg = cfg.build_model().cfg
                if args.config == "gpt2_124m":
                    seq = args.seq_len or 1024
                else:
                    seq = mcfg.max_positions
                ids = np.fromfile(tok, dtype=dtype).astype(np.int32)
                if ids.size and int(ids.max()) >= mcfg.vocab_size:
                    # Same loud refusal as the train path: out-of-range
                    # ids clip under jit and yield a finite, meaningless
                    # perplexity with no diagnostic.
                    raise SystemExit(
                        f"{tok} holds token ids up to {int(ids.max())} "
                        f"but the model vocab is {mcfg.vocab_size}; "
                        f"re-pack the val split with the matching "
                        f"tokenizer")
                win = seq + 1
                n_win = ids.size // win
                if n_win < 1:
                    raise SystemExit(f"{tok}: {ids.size} tokens is fewer "
                                     f"than one {win}-token eval window")
                ids = ids[:n_win * win].reshape(n_win, win)
                bs = min(batch_size, n_win)

                def batches(ids=ids, bs=bs):
                    # Full batches, then the remainder as a smaller final
                    # batch (one extra jit trace) — exhaustive coverage,
                    # as the log line claims.
                    full = (ids.shape[0] // bs) * bs
                    for i in range(0, full, bs):
                        yield {"tokens": ids[i:i + bs]}
                    if full < ids.shape[0]:
                        yield {"tokens": ids[full:]}

                print(f"eval: {n_win} held-out windows from {tok}",
                      file=sys.stderr)
                it = batches()
                if args.config == "bert_base_zero1":
                    from nezha_tpu.data.mlm import mlm_batches_from_tokens
                    mask_token = _resolve_mlm_mask_token(args, mcfg, tok,
                                                         ids)
                    it = mlm_batches_from_tokens(
                        ({"tokens": b["tokens"][:, :-1]} for b in it),
                        vocab_size=mcfg.vocab_size,
                        mask_token=mask_token, seed=args.seed)
                return it, None, cfg.eval_stat
    if cfg.eval_batches is not None:
        return cfg.eval_batches(batch_size), None, cfg.eval_stat
    return None, None, None


def _wrap_model_overrides(cfg, **overrides) -> None:
    """Rebind cfg.build_model (and sp_model) with extra model-config kwargs
    — the shared core of the gpt2 knobs (--moe-experts, --remat). Wraps
    compose; a duplicated kwarg fails loudly at build time."""
    build0 = cfg.build_model
    cfg.build_model = lambda **ov: build0(**overrides, **ov)
    if cfg.sp_model is not None:
        sp0 = cfg.sp_model
        cfg.sp_model = lambda impl, **ov: sp0(impl, **overrides, **ov)


def _make_batch_sharder(mesh, group):
    """dp/zero1 batch placement: single-process hosts hold the whole global
    batch (device_put row-split); multi-process hosts hold only their local
    shard rows, assembled into the global array with zero inter-host
    transfer (pairs with _data_source's per-rank sharded loading)."""
    from nezha_tpu import parallel

    if group is not None and group.world_size > 1:
        return lambda b: parallel.shard_batch_process_local(mesh, b)
    return lambda b: parallel.shard_batch(mesh, b)


def _parse_profile_steps(spec: str):
    """Validate START:COUNT (pure argv parsing — called before any setup so
    a typo can't strand multi-host peers past the rendezvous)."""
    m = re.match(r"^(\d+):(\d+)$", spec)
    if not m or int(m.group(1)) < 1 or int(m.group(2)) < 1:
        # START >= 1: the window opens before step START is dispatched,
        # and the first step is 1.
        raise SystemExit(f"--profile-steps takes START:COUNT with START "
                         f">= 1 and COUNT >= 1 (e.g. 10:3), got {spec!r}")
    return int(m.group(1)), int(m.group(2))


def run(args) -> Dict[str, float]:
    """Argv-validated entry. With ``--run-dir`` the whole run executes
    inside a telemetry run scope: the registry turns on, per-window
    metrics/spans stream into the directory, and ``summary.json`` lands on
    every exit path (success or raise) — `nezha-telemetry RUN_DIR` renders
    the report."""
    from nezha_tpu import faults
    # Chaos drills (docs/RUNBOOK.md §9): NEZHA_FAULT_PLAN arms the
    # registered fault points (e.g. checkpoint.save) for this run —
    # restored on exit so embedded callers don't leak the plan
    # (restoring an unchanged plan is a no-op).
    prev_plan = faults.active()
    faults.install_from_env()
    try:
        return _run_checked(args)
    finally:
        faults.install(prev_plan)


def _run_checked(args) -> Dict[str, float]:
    if args.trace_dir:
        # --trace-dir is the observability-workflow spelling of
        # --profile-dir (XProf/XLA trace window; see docs/RUNBOOK.md §7).
        if args.profile_dir and args.profile_dir != args.trace_dir:
            raise SystemExit("--trace-dir is an alias for --profile-dir; "
                             "pass one of them")
        args.profile_dir = args.trace_dir
    if not args.run_dir:
        return _run_traced(args)
    import os

    from nezha_tpu import obs
    run_dir = args.run_dir
    if args.coordinator:
        # Multi-process launch: every process captures into its own
        # subdirectory — the sink truncates its streams on open, so two
        # ranks sharing one dir would destroy each other's capture. Rank
        # is only assigned at the rendezvous (inside the run scope), so
        # the pre-join identity is the rank hint, else the PID.
        sub = (f"rank{args.rank_hint}" if args.rank_hint >= 0
               else f"pid{os.getpid()}")
        run_dir = os.path.join(run_dir, sub)
    obs.start_run(run_dir, meta={
        "config": args.config, "steps": args.steps,
        "engine": args.engine, "parallel": args.parallel,
        "model_preset": args.model_preset})
    try:
        return _run_traced(args)
    finally:
        obs.end_run()


def _run_traced(args) -> Dict[str, float]:
    if args.ckpt_keep is not None and args.ckpt_keep <= 0:
        raise SystemExit(f"--ckpt-keep must be >= 1 (got {args.ckpt_keep}); "
                         f"omit it to keep all checkpoints")
    if args.profile_steps:
        if not args.profile_dir:
            raise SystemExit("--profile-steps needs --profile-dir for the "
                             "trace output")
        _parse_profile_steps(args.profile_steps)
    if args.clip_norm is not None:
        # Pure-argv validation BEFORE the rendezvous (a post-join
        # SystemExit would strand multi-host peers in their next
        # collective); the wrap itself happens after the parallel mode is
        # known, since ZeRO-1 needs the cross-rank norm.
        if not args.clip_norm > 0:  # also catches NaN (every compare False)
            raise SystemExit(f"--clip-norm must be > 0, got {args.clip_norm}")
        if args.engine == "graph" and args.parallel in ("dp", "zero1"):
            raise SystemExit("--clip-norm with the graph engine's dp/zero1 "
                             "modes is unsupported: the clip must see the "
                             "REDUCED gradients, but their collectives "
                             "live inside the update graphs; use "
                             "single-device graph or the module engine")
    if args.eval_every is not None and args.eval_every < 1:
        raise SystemExit(f"--eval-every must be >= 1, got {args.eval_every}")
    if args.eval_batches is not None and args.eval_batches < 1:
        # An empty eval pass would raise MID-training under --eval-every,
        # after real progress — reject it before anything starts.
        raise SystemExit(f"--eval-batches must be >= 1, got "
                         f"{args.eval_batches}")
    if args.lr is not None and not args.optimizer:
        raise SystemExit("--lr only applies with --optimizer (each config's "
                         "default optimizer bakes its own tuned schedule)")
    if args.optimizer:
        if args.engine == "graph":
            raise SystemExit("the graph engine authors its optimizer update "
                             "in the IR (momentum/adamw programs); "
                             "--optimizer cannot swap it")
        if args.lr is None:
            raise SystemExit("--optimizer needs --lr (peak learning rate "
                             "for the warmup+cosine schedule)")
        if not args.lr > 0:  # also catches NaN
            raise SystemExit(f"--lr must be > 0, got {args.lr}")
    if args.on_failure == "rejoin":
        # All argv-level: reject before the rendezvous can strand peers.
        if not args.rejoin_timeout > 0:  # also catches NaN
            raise SystemExit(f"--rejoin-timeout must be > 0, got "
                             f"{args.rejoin_timeout}")
        if not args.coordinator:
            raise SystemExit("--on-failure rejoin needs --coordinator "
                             "(failure detection is the coordinator's "
                             "heartbeat)")
        if not args.ckpt_dir:
            raise SystemExit("--on-failure rejoin needs --ckpt-dir: "
                             "recovery reloads the rescue checkpoint")
        if not args.no_jax_distributed:
            raise SystemExit("--on-failure rejoin requires "
                             "--no-jax-distributed: XLA's distributed "
                             "runtime cannot absorb a restarted process "
                             "mid-run — with jax.distributed, use "
                             "--on-failure stop and a supervisor relaunch "
                             "(training resumes from --ckpt-dir)")
    group, coord = _join_world(args)

    import jax

    from nezha_tpu.cli.common import setup_jax
    setup_jax(args)

    from nezha_tpu import parallel
    from nezha_tpu.runtime import Prefetcher
    from nezha_tpu.train import checkpoint as ckpt
    from nezha_tpu.train import sharded_checkpoint as sckpt
    from nezha_tpu.train.loop import (
        Trainer,
        device_span,
        init_train_state,
        make_train_step,
    )

    cfg = _configs()[args.config]
    if args.model_preset == "tiny":
        for field, value in cfg.tiny.items():
            setattr(cfg, field, value)
    batch_size = args.batch_size or cfg.default_batch

    if args.moe_experts:
        # Mixture-of-experts GPT-2: every other block's MLP becomes a
        # top-k routed expert layer; lm_loss adds the load-balance aux.
        if args.config != "gpt2_124m":
            raise SystemExit("--moe-experts applies to gpt2_124m")
        if args.engine == "graph":
            raise SystemExit("--moe-experts is not expressible in the "
                             "graph engine's GPT-2 program; drop --engine "
                             "graph")
        if args.parallel == "pp":
            raise SystemExit("--moe-experts cannot pipeline (MoE blocks "
                             "make the stage slabs heterogeneous); use "
                             "--parallel dp/zero1/sp, or gspmd with an ep "
                             "mesh axis (--mesh dp=X,tp=Y,ep=Z)")
        _wrap_model_overrides(cfg, moe_experts=args.moe_experts)

    if args.optimizer:
        # (Pairing/value/engine checks ran pre-rendezvous; the lars/lamb x
        # zero1 guard runs post-degrade below, where the real mode is known.)
        from nezha_tpu import optim as optim_mod
        factories = {
            "sgd": optim_mod.sgd,
            "momentum": lambda lr: optim_mod.momentum(
                lr, beta=0.9, weight_decay=1e-4),
            "adamw": lambda lr, **kw: optim_mod.adamw(lr,
                                                      weight_decay=0.1,
                                                      **kw),
            "lars": lambda lr: optim_mod.lars(lr, weight_decay=1e-4),
            "lamb": lambda lr, **kw: optim_mod.lamb(lr, weight_decay=0.01,
                                                    **kw),
            "adafactor": optim_mod.adafactor,
        }
        factory = factories[args.optimizer]
        cfg.build_optimizer = lambda steps, **kw: factory(
            optim_mod.warmup_cosine_schedule(
                args.lr, min(100, max(1, steps // 10)), max(steps, 200)),
            **kw)

    if args.graph_bf16:
        if args.engine != "graph" or args.config != "gpt2_124m":
            raise SystemExit("--graph-bf16 applies to --engine graph with "
                             "gpt2_124m (the bf16 policy authored in the "
                             "IR; the module engine's presets carry their "
                             "own policies)")

    if args.wd_exclude_1d:
        # The standard GPT-2/BERT recipe: no decoupled weight decay on
        # norm scales/biases (any leaf with ndim < 2). Composes with the
        # default AdamW schedules and with --optimizer adamw/lamb.
        if args.engine == "graph":
            raise SystemExit("--wd-exclude-1d: the graph engine's "
                             "IR-authored update decays every leaf")
        if args.optimizer and args.optimizer not in ("adamw", "lamb"):
            raise SystemExit(f"--wd-exclude-1d needs a masked-decay "
                             f"optimizer (adamw/lamb), not "
                             f"{args.optimizer}")
        if not args.optimizer and args.config not in ("gpt2_124m",
                                                      "bert_base_zero1"):
            raise SystemExit("--wd-exclude-1d applies to the AdamW "
                             "configs (gpt2_124m, bert_base_zero1) or "
                             "with --optimizer adamw/lamb")
        from nezha_tpu import optim as optim_mod
        _build_opt0 = cfg.build_optimizer
        cfg.build_optimizer = lambda steps: _build_opt0(
            steps, mask=optim_mod.matrix_decay_mask)

    if args.grad_accum is not None:
        if args.grad_accum < 1:
            raise SystemExit(f"--grad-accum must be >= 1, got "
                             f"{args.grad_accum}")
        if args.engine == "graph" and args.grad_accum > 1:
            raise SystemExit("--grad-accum is an optimizer wrapper the "
                             "graph engine's IR-authored update does not "
                             "express; drop --engine graph")
        # (The wrap itself happens late, composed outside --clip-norm.)

    if args.dropout is not None:
        if args.config != "gpt2_124m":
            raise SystemExit("--dropout applies to gpt2_124m")
        if args.engine == "graph":
            raise SystemExit("the graph engine's GPT-2 program has no "
                             "dropout path; drop --engine graph")
        if not 0.0 <= args.dropout < 1.0:
            raise SystemExit(f"--dropout must be in [0, 1), got "
                             f"{args.dropout}")
        _wrap_model_overrides(cfg, dropout=args.dropout)

    if args.label_smoothing:
        # Standard ImageNet recipe: train against (1-eps)*one_hot + eps/V.
        if args.config not in ("mlp_mnist",) + _IMAGE_CONFIGS:
            raise SystemExit("--label-smoothing applies to the integer-"
                             "label CE configs (mlp_mnist, "
                             + ", ".join(_IMAGE_CONFIGS) + ")")
        if args.engine == "graph":
            raise SystemExit("the graph engine's programs author the plain "
                             "CE; drop --engine graph")
        if not 0.0 < args.label_smoothing < 1.0:
            raise SystemExit(f"--label-smoothing must be in (0, 1), got "
                             f"{args.label_smoothing}")
        from nezha_tpu import ops
        eps = args.label_smoothing
        cfg.loss_fn = lambda logits, b: \
            ops.softmax_cross_entropy_with_integer_labels(
                logits, b["label"], label_smoothing=eps)

    if args.mlm_mask_token is not None and (
            args.config != "bert_base_zero1" or not args.data_dir):
        raise SystemExit("--mlm-mask-token applies to bert_base_zero1 "
                         "with --data-dir (the dynamic-MLM data path)")

    if args.remat:
        # Block rematerialization: the long-context/big-batch memory knob
        # (jax.checkpoint per transformer block / ResNet bottleneck; see
        # GPT2Config.remat, ResNet(remat=...)).
        if args.config not in ("gpt2_124m",) + _IMAGE_CONFIGS:
            raise SystemExit("--remat applies to gpt2_124m and the image "
                             "configs")
        if args.engine == "graph":
            raise SystemExit("--remat is a jax.checkpoint knob; the graph "
                             "engine does not rematerialize")
        _wrap_model_overrides(cfg, remat=True)

    if args.scan_layers:
        # Scan trunk: a params-layout change (h_scan, leading layer dim),
        # so restrict to the paths whose param handling is layout-agnostic
        # and parity-tested; gspmd TP rules and the pipeline/sp builders
        # address h{i} names explicitly.
        if args.config not in ("gpt2_124m", "bert_base_zero1"):
            raise SystemExit("--scan-layers applies to gpt2_124m / "
                             "bert_base_zero1")
        if args.engine == "graph":
            raise SystemExit("--scan-layers is a module-engine knob; the "
                             "graph engine authors its own trunk IR")
        eff = cfg.parallel_mode if args.parallel == "config" \
            else args.parallel
        if eff not in ("single", "dp", "zero1", "gspmd", "sp"):
            raise SystemExit("--scan-layers supports --parallel "
                             "single/dp/zero1/gspmd/sp (the pp builder "
                             "addresses unrolled h{i} names)")
        _wrap_model_overrides(cfg, scan_layers=True)

    if args.seq_len:
        # Long-context override: resize position table + data together.
        # With --parallel sp the sequence shards over the sp axis, so
        # per-chip activation memory stays O(seq_len / sp).
        if args.config != "gpt2_124m":
            raise SystemExit("--seq-len applies to gpt2_124m")
        sl = args.seq_len
        build0, sp0, batches0 = cfg.build_model, cfg.sp_model, cfg.batches
        eval0 = cfg.eval_batches
        cfg.build_model = lambda: build0(max_positions=sl)
        if sp0 is not None:
            cfg.sp_model = lambda impl, **ov: sp0(impl, max_positions=sl,
                                                  **ov)
        cfg.batches = lambda bs: batches0(bs, seq_len=sl)
        if eval0 is not None:
            cfg.eval_batches = lambda bs: eval0(bs, seq_len=sl)

    # --- graph-IR engine (north star: Graph -> StableHLO -> Executor) -----
    # Resolved before any parallel-mode/mesh logic: the engine is single-
    # device by design, so it must neither trip the multi-device degrade
    # warning nor build a mesh it will never use.
    if args.engine == "graph":
        graph_mode = "single" if args.parallel == "config" else args.parallel
        if graph_mode not in ("single", "dp", "zero1"):
            raise SystemExit(f"--engine graph supports --parallel dp "
                             f"(IR all_reduce) or zero1 (IR reduce_scatter "
                             f"+ all_gather) or single-device, not "
                             f"{graph_mode!r}")
        if graph_mode == "zero1":
            if args.config != "mlp_mnist":
                raise SystemExit("graph-engine zero1 is authored for "
                                 "mlp_mnist (graph/programs.py "
                                 "zero1_update_graph); other configs run "
                                 "the module engine's zero1")
            if group is not None and group.world_size > 1:
                raise SystemExit("graph-engine zero1 is single-controller "
                                 "(its flat dp-sharded state cannot be "
                                 "fetched/checkpointed across OS "
                                 "processes); multi-process zero1 runs the "
                                 "module engine")
        if graph_mode == "single" and args.mesh:
            raise SystemExit("--mesh needs --parallel dp/zero1 with the "
                             "graph engine (single-device IR does not "
                             "partition)")
        if args.grad_allreduce != "fp32":
            raise SystemExit("--grad-allreduce int8 is the module engine's "
                             "dp/zero1 wire; the graph engine's all-reduce "
                             "is an IR op (fp32 only)")
        if args.sp_flash != "auto":
            raise SystemExit("--sp-flash tunes the sequence-parallel "
                             "attention kernels; it needs --parallel sp "
                             "(module engine)")
        import numpy as _np

        from nezha_tpu.graph import programs
        mode, mesh = graph_mode, None
        if mode in ("dp", "zero1") and len(jax.devices()) == 1:
            raise SystemExit(f"--engine graph --parallel {mode} needs more "
                             f"than the 1 visible device")
        if mode in ("dp", "zero1"):
            mesh_axes = _parse_mesh(args.mesh) or _parse_mesh("dp=-1")
            if list(mesh_axes) != ["dp"]:
                raise SystemExit(f"graph-engine {mode} consumes mesh axis "
                                 f"'dp' only; got {list(mesh_axes)}")
            mesh = parallel.make_mesh(mesh_axes)
            world = mesh.shape["dp"]
            if batch_size % world:
                raise SystemExit(f"--batch-size {batch_size} is not "
                                 f"divisible by mesh axis dp={world} (it is "
                                 f"the GLOBAL batch; shards must be equal)")
        model = cfg.build_model()
        optimizer = cfg.build_optimizer(args.steps)
        rng = jax.random.PRNGKey(args.seed)
        if args.config == "mlp_mnist":
            dims = [784, 256, 256, 10]
            # dp: _make_batch_sharder pairs with _data_source, so
            # multi-process launches feed LOCAL rows assembled
            # process-locally like module-engine dp. zero1 is validated
            # single-process above (its state fetch is single-controller).
            onehot = programs.onehot_shard_fn(dims[-1])
            if mode == "zero1":
                state = programs.init_graph_mlp_zero1_state(dims, rng, mesh)
                step_fn = programs.make_mlp_graph_zero1_train_step(
                    dims, batch_size, lr=0.1, mesh=mesh)
                shard = lambda b: parallel.shard_batch(mesh, onehot(b))
            elif mode == "dp":
                state = programs.init_graph_mlp_state(dims, rng)
                step_fn = programs.make_mlp_graph_dp_train_step(
                    dims, batch_size, lr=0.1, mesh=mesh)
                shard = onehot  # placement hoisted below (all dp configs)
            else:
                state = programs.init_graph_mlp_state(dims, rng)
                step_fn = programs.make_mlp_graph_train_step(
                    dims, batch_size, lr=0.1, clip_norm=args.clip_norm)
                shard = onehot
        elif args.config in ("resnet50_imagenet", "wrn101_large_batch"):
            if args.eval or args.eval_every:
                raise SystemExit("graph-engine ResNet runs training-mode "
                                 "batch stats only (no running BN stats); "
                                 "drop --eval/--eval-every")
            state = programs.init_graph_resnet_state(model, rng)
            if mode == "dp":
                step_fn = programs.make_resnet_graph_dp_train_step(
                    model, batch_size, lr=0.1, mesh=mesh)
                shard = programs.image_shard_fn()
            else:
                step_fn = programs.make_resnet_graph_train_step(
                    model, lr=0.1, clip_norm=args.clip_norm)
                shard = programs.image_shard_fn()
        elif args.config == "bert_base_zero1":
            state = programs.init_graph_bert_state(model, rng)
            sched = cfg.graph_opt["schedule"](args.steps)
            step_fn = programs.make_bert_graph_train_step(
                model, lambda t: float(sched(_np.int32(t))),
                weight_decay=cfg.graph_opt["weight_decay"],
                clip_norm=args.clip_norm,
                mesh=mesh if mode == "dp" else None)
            shard = programs.bert_shard_fn()
        else:  # gpt2_124m: the transformer authored in the IR
            state = programs.init_graph_gpt2_state(model, rng)
            sched = cfg.graph_opt["schedule"](args.steps)
            step_fn = programs.make_gpt2_graph_train_step(
                model, lambda t: float(sched(_np.int32(t))),
                weight_decay=cfg.graph_opt["weight_decay"],
                clip_norm=args.clip_norm,
                mesh=mesh if mode == "dp" else None,
                compute_dtype="bfloat16" if args.graph_bf16
                else "float32")
            shard = programs.lm_shard_fn()
        if mode == "dp":
            # One placement composition for every graph-dp config:
            # _make_batch_sharder pairs with _data_source so multi-process
            # launches feed LOCAL rows assembled process-locally.
            _base_shard = shard
            _place = _make_batch_sharder(mesh, group)
            shard = lambda b: _place(_base_shard(b))
        start_step = 0
        if args.ckpt_dir:
            restored, start_step = ckpt.try_restore(args.ckpt_dir, state)
            if restored is not None:
                state = restored
                print(f"resumed from step {start_step}", file=sys.stderr)
        if mode == "dp":
            state = parallel.replicate(mesh, state)
        elif mode == "zero1" and start_step:
            # A resume restored numpy leaves; re-shard the flat 1-D state
            # over dp. (Fresh init is already placed — no gather round-trip.)
            from jax.sharding import NamedSharding, PartitionSpec as _P
            _sh = NamedSharding(mesh, _P("dp"))
            state = jax.tree_util.tree_map(
                lambda x: jax.device_put(np.asarray(x), _sh), state)
        save_fn = None
    else:
        mode = cfg.parallel_mode if args.parallel == "config" else args.parallel
        if mode == "single" and args.mesh:
            raise SystemExit("--mesh has no effect in single-device mode; "
                             "drop it or pick a --parallel mode that "
                             "consumes it")
        # An EXPLICIT all-ones mesh (e.g. --mesh dp=1,sp=1) fits one device
        # by construction and must run the requested mode — it is the
        # 1-chip smoke of a parallel path (kernel compiles, shard_map
        # wiring), not a mis-launch.
        _req = _parse_mesh(args.mesh)
        _req_size = 1
        for _v in (_req or {"": -1}).values():
            _req_size *= _v  # any -1 ("all devices") counts as multi
        if (mode != "single" and len(jax.devices()) == 1
                and _req_size != 1):
            if args.parallel != "config" or args.mesh:
                # An explicit multi-device request that one device cannot
                # meet is an error: a mis-launched job must not "succeed"
                # at 1/Nth scale.
                raise SystemExit(
                    f"--parallel {mode}"
                    + (f" --mesh {args.mesh}" if args.mesh else "")
                    + " needs more than the 1 visible device")
            # Only a config's DEFAULT mode degrades (one chip is a normal
            # place to run gpt2_124m), and never silently.
            print(f"WARNING: config {args.config!r} requests parallel mode "
                  f"{mode!r} but only 1 device is visible; running "
                  f"single-device (check your mesh/launch if this is a "
                  f"multi-chip job)", file=sys.stderr)
            mode = "single"
        # After the degrade: a mode that will not run the dp/zero1 wire
        # cannot consume the int8 request — reject, don't ignore (the
        # degrade would otherwise silently swap exact fp32 semantics in).
        if args.grad_allreduce != "fp32" and mode not in ("dp", "zero1"):
            raise SystemExit("--grad-allreduce int8 is the dp/zero1 "
                             f"gradient wire format; mode {mode!r} does "
                             "not consume it (reject, don't ignore)")
        if args.sp_flash != "auto" and mode != "sp":
            raise SystemExit(f"--sp-flash tunes the sequence-parallel "
                             f"attention kernels; mode {mode!r} does not "
                             f"consume it (reject, don't ignore)")
        if args.optimizer in ("lars", "lamb") and mode == "zero1":
            raise SystemExit(f"--optimizer {args.optimizer} computes "
                             f"layerwise trust ratios, which ZeRO-1's flat "
                             f"per-rank chunks cannot preserve; use "
                             f"--parallel dp (or adamw/momentum with zero1)")
        if args.wd_exclude_1d and mode in ("zero1", "pp"):
            raise SystemExit("--wd-exclude-1d: this mode's flat/stacked "
                             "param layout (zero1 chunks, pp stage slabs) "
                             "erases the leaf shapes the ndim-based decay "
                             "mask keys on; use --parallel dp/single/gspmd")

        # Mesh axes are validated against the chosen mode: an axis the mode
        # cannot consume is an error, never silently ignored — and every
        # axis the mode's shard/step functions hardcode must be present
        # (all modes shard the batch over "dp"; pass dp=1 to opt out of
        # data parallelism).
        mode_axes = {"single": (), "dp": ("dp",), "zero1": ("dp",),
                     "gspmd": ("dp", "tp"), "pp": ("dp", "pp"),
                     "sp": ("dp", "sp")}
        mode_default_mesh = {"dp": "dp=-1", "zero1": "dp=-1",
                             "gspmd": "dp=1,tp=-1", "pp": "dp=1,pp=-1",
                             "sp": "dp=1,sp=-1"}
        if args.moe_experts and mode == "gspmd":
            # MoE under GSPMD adds the expert axis: dp x tp x ep (tp=1 to
            # disable tensor parallelism; experts shard over ep).
            mode_axes["gspmd"] = ("dp", "tp", "ep")
            mode_default_mesh["gspmd"] = "dp=1,tp=1,ep=-1"
        mesh = None
        if mode != "single":
            mesh_axes = (_parse_mesh(args.mesh)
                         or _parse_mesh(mode_default_mesh[mode]))
            unusable = [a for a in mesh_axes if a not in mode_axes[mode]]
            if unusable:
                raise SystemExit(
                    f"parallel mode {mode!r} cannot use mesh axis(es) "
                    f"{unusable} (it consumes {list(mode_axes[mode])}); "
                    f"pass --parallel to select the mode that uses them")
            missing = [a for a in mode_axes[mode] if a not in mesh_axes]
            if missing:
                raise SystemExit(
                    f"parallel mode {mode!r} needs mesh axis(es) {missing} "
                    f"(use size 1 to disable an axis); got "
                    f"{list(mesh_axes)}")
            mesh = parallel.make_mesh(mesh_axes)
            ep_size = mesh.shape.get("ep")
            if ep_size and args.moe_experts % ep_size:
                raise SystemExit(
                    f"--moe-experts {args.moe_experts} is not divisible by "
                    f"mesh axis ep={ep_size}; expert stacks shard over ep "
                    f"(pass --mesh dp=X,tp=Y,ep=Z with Z dividing the "
                    f"expert count)")

        if mode == "sp":
            if cfg.sp_model is None:
                raise SystemExit(f"config {args.config!r} has no sequence-"
                                 f"parallel model; --parallel sp supports: "
                                 f"gpt2_124m")
            model = cfg.sp_model(
                args.attn_impl,
                sp_use_flash={"auto": None, "on": True,
                              "off": False}[args.sp_flash])
        else:
            model = cfg.build_model()
        if args.clip_norm is not None:
            # ZeRO-1's optimizer sees per-rank gradient SHARDS, so the
            # clip's norm must psum over dp; every other mode's optimizer
            # sees full gradients.
            from nezha_tpu import optim as optim_mod
            clip_build = cfg.build_optimizer
            clip_axis = "dp" if mode == "zero1" else None
            cfg.build_optimizer = lambda steps: optim_mod.with_grad_clipping(
                clip_build(steps), args.clip_norm, axis_name=clip_axis)
        if args.grad_accum is not None and args.grad_accum > 1:
            # Outside the clip: accumulate RAW micro-grads, clip the
            # flushed mean. The inner optimizer (and its LR schedule)
            # steps once per FLUSH — size the horizon to real updates or
            # the cosine never finishes.
            from nezha_tpu import optim as optim_mod
            acc_build = cfg.build_optimizer
            cfg.build_optimizer = lambda steps: optim_mod.accumulate_gradients(
                acc_build(max(1, steps // args.grad_accum)),
                args.grad_accum)
        optimizer = cfg.build_optimizer(args.steps)
        rng = jax.random.PRNGKey(args.seed)

        # --- state + per-mode step/shard/checkpoint format ----------------
        # ZeRO-1/GSPMD/pipeline state is sharded by construction, so those
        # modes use the per-shard checkpoint format (restore needs the
        # sharded template, hence after layout); the replicated-state modes
        # (single/dp/sp) restore plain npz before layout. Pipeline state
        # never materializes a dense optimizer state at all (its slots are
        # born sharded over the stage slabs), so it inits from variables
        # alone below.
        start_step = 0
        save_fn = None
        if mode != "pp":
            state = init_train_state(model, optimizer, rng)
            if mode in ("single", "dp", "sp") and args.ckpt_dir:
                restored, start_step = ckpt.try_restore(args.ckpt_dir, state)
                if restored is not None:
                    state = restored
                    print(f"resumed from step {start_step}", file=sys.stderr)

        if mode == "single":
            step_fn = make_train_step(model, optimizer, cfg.loss_fn)
            shard = None
        elif mode == "dp":
            state = parallel.replicate(mesh, state)
            step_fn = parallel.make_dp_train_step(
                model, optimizer, cfg.loss_fn, mesh,
                grad_reduce=args.grad_allreduce)
            shard = _make_batch_sharder(mesh, group)
        elif mode == "sp":
            from nezha_tpu.parallel import sequence_parallel as sp_mod
            state = parallel.replicate(mesh, state)
            step_fn = sp_mod.make_sp_train_step(model, optimizer, mesh)
            shard = lambda b: sp_mod.shard_lm_batch(mesh, b)
        elif mode == "gspmd":
            if cfg.tp_rules is None:
                raise SystemExit(
                    f"config {args.config!r} has no tensor-parallel rule "
                    f"table; --parallel gspmd supports: gpt2_124m, "
                    f"bert_base_zero1")
            rules = cfg.tp_rules
            if args.moe_experts:
                from nezha_tpu.parallel.expert import gpt2_moe_gspmd_rules
                rules = gpt2_moe_gspmd_rules(cfg.tp_rules)
            if args.scan_layers:
                # Stacked-trunk layout: same rule table, specs computed on
                # the unrolled view with a leading layer dim (the
                # canonical scan-over-layers + GSPMD TP shape).
                prefix, key = (("h", "h_scan") if args.config == "gpt2_124m"
                               else ("layers", "layers_scan"))
                specs = parallel.scan_param_specs(
                    state["variables"]["params"], rules,
                    model.cfg.num_layers, prefix, key, strict=True)
            else:
                specs = parallel.param_specs_from_rules(
                    state["variables"]["params"], rules, strict=True)
            state = parallel.shard_train_state(state, mesh, specs)
            save_fn = sckpt.save_sharded
            step_fn = parallel.make_gspmd_train_step(
                model, optimizer, cfg.loss_fn, mesh, specs)
            from nezha_tpu.parallel.gspmd import shard_batch_gspmd
            shard = lambda b: shard_batch_gspmd(mesh, b)
        elif mode == "pp":
            if cfg.pipeline_spec is None:
                raise SystemExit(f"config {args.config!r} has no pipeline "
                                 f"spec; --parallel pp supports: gpt2_124m")
            from nezha_tpu.parallel import pipeline as pp_mod
            pspec = cfg.pipeline_spec(model)
            state = pp_mod.init_pipeline_state(
                model.init(rng), pspec, optimizer, mesh, rng)
            save_fn = sckpt.save_sharded
            # dropout_rng/remat resolve from the spec's own fields (set
            # from the model config by gpt2_pipeline_spec).
            step_fn = pp_mod.make_pipeline_train_step(
                pspec, optimizer, cfg.loss_fn, mesh,
                num_microbatches=args.microbatches,
                dropout_rng=bool(pspec.dropout))
            shard = lambda b: parallel.shard_batch(mesh, b)
        elif mode == "zero1":
            variables = state["variables"]
            state = {
                "variables": parallel.replicate(mesh, variables),
                "opt_state": parallel.zero1_init_opt_state(
                    optimizer, variables["params"], mesh),
                "rng": parallel.replicate(mesh, state["rng"]),
            }
            save_fn = sckpt.save_sharded
            step_fn = parallel.make_zero1_train_step(
                model, optimizer, cfg.loss_fn, mesh,
                grad_reduce=args.grad_allreduce)
            shard = _make_batch_sharder(mesh, group)
        else:
            raise ValueError(mode)

        # Sharded-state modes restore AFTER layout: the per-shard format
        # rebuilds each leaf against the live template sharding (one shared
        # block — the gspmd/pp/zero1 layouts all restore identically).
        if save_fn is sckpt.save_sharded and args.ckpt_dir:
            restored, start_step = sckpt.try_restore_sharded(
                args.ckpt_dir, state)
            if restored is None and mode == "zero1":
                # Legacy dense zero1 checkpoints (pre-sharded-format CLI)
                # restore into the same laid-out template.
                restored, start_step = ckpt.try_restore(args.ckpt_dir, state)
            if restored is not None:
                state = restored
                print(f"resumed from step {start_step} (sharded)",
                      file=sys.stderr)

    # Sharded saves go through the AsyncCheckpointer by default: the step
    # path pays only the device->host shard copies; file IO runs off-thread
    # (wait() commits before the failure-path raise and after the final
    # save).
    async_ckpt = None
    if save_fn is sckpt.save_sharded and args.ckpt_dir:
        async_ckpt = sckpt.AsyncCheckpointer()
        save_fn = async_ckpt.save
    # Retention (--ckpt-keep) flows through Trainer.checkpoint_keep for
    # every save path — the Trainer forwards keep_last to the save_fn.

    # --- loop (one shared Trainer for every mode, so failure detection /
    # checkpoint-before-raise is live in real CLI runs) --------------------
    # Multi-process data sharding pairs with process-local batch assembly,
    # which only the dp/zero1 sharders do; other modes keep the documented
    # identical-stream semantics of shard_batch.
    data_group = (group if group is not None and group.world_size > 1
                  and mode in ("dp", "zero1") else None)
    if data_group is not None and batch_size % data_group.world_size:
        raise SystemExit(
            f"--batch-size {batch_size} must be divisible by the process "
            f"world size {data_group.world_size} (it is the GLOBAL batch; "
            f"each host loads batch/world local rows)")
    source, close_source = _data_source(args, cfg, batch_size,
                                        group=data_group)
    prefetch = Prefetcher(source, depth=args.prefetch)
    from nezha_tpu.utils import MetricsLogger
    metrics_log = MetricsLogger(args.metrics_file) if args.metrics_file else None

    def log_metrics(step_no: int, metrics: Dict[str, float]) -> None:
        if args.log_memory:
            # Live/peak HBM per step (empty off-TPU: CPU exposes no stats).
            from nezha_tpu.tensor import memory_metrics
            metrics = {**metrics, **memory_metrics()}
        print(json.dumps(metrics), file=sys.stderr)
        if metrics_log:
            metrics_log.log(step_no, metrics)

    tracer = None
    if args.profile_steps:
        # Validated at the top of run(); the Tracer itself is cheap.
        start, count = _parse_profile_steps(args.profile_steps)
        from nezha_tpu.utils import Tracer
        tracer = Tracer(args.profile_dir, start_step=start, num_steps=count)

    if args.on_failure == "rejoin" and (mode not in ("single", "dp", "sp")
                                        or args.engine == "graph"):
        # The recovery reload goes through Trainer.initialize's plain-npz
        # restore, which pairs with the replicated-state module-engine
        # modes; sharded-state modes (zero1/gspmd/pp) and the graph
        # engine's own state layouts recover via supervisor restart.
        raise SystemExit(f"--on-failure rejoin supports the "
                         f"replicated-state module-engine modes "
                         f"(single/dp/sp); got mode {mode!r}, engine "
                         f"{args.engine!r} — use --on-failure stop with a "
                         f"supervisor relaunch")
    # Where the arrays REALLY live, reported with the final metrics: a
    # mis-launch that quietly ran on one device shows 1 here whatever the
    # mesh said (chip_smoke.py asserts on these).
    placement = {"batch_devices": 1}
    if shard is not None:
        place_batch = shard

        def shard(b):
            out = place_batch(b)
            placement["batch_devices"] = max(device_span(out), 1)
            return out

    trainer = Trainer(
        model, optimizer, cfg.loss_fn,
        checkpoint_dir=args.ckpt_dir,
        checkpoint_every=args.ckpt_every,
        log_every=args.log_every,
        metric_logger=log_metrics,
        tracer=tracer,
        process_group=group,
        failure_check_every=args.failure_check_every if group is not None
        else 0,
        failure_mode=args.on_failure,
        rejoin_timeout_s=args.rejoin_timeout,
        step_fn=step_fn,
        shard_fn=shard,
        save_fn=save_fn,
        save_wait=async_ckpt.wait if async_ckpt is not None else None,
        checkpoint_keep=args.ckpt_keep,
        examples_per_step=batch_size)
    trainer.state = state
    trainer.global_step = start_step

    eval_cache: Dict[str, Any] = {}  # jitted eval step reused across passes
    whole_run_trace = args.profile_dir and tracer is None
    if whole_run_trace:
        import os as _os
        _os.makedirs(args.profile_dir, exist_ok=True)
        jax.profiler.start_trace(args.profile_dir)

    last: Dict[str, float] = {}
    try:
        if args.eval_every:
            # Periodic eval: train in chunks aligned to GLOBAL-step
            # multiples of --eval-every (same cadence convention as
            # --ckpt-every/--log-every, so a resumed run's eval points
            # line up with the pre-restart stream), full eval pass between
            # chunks. The final pass happens at the tail with the
            # end-of-run --eval handling.
            done = 0
            while done < args.steps:
                to_boundary = (args.eval_every
                               - trainer.global_step % args.eval_every)
                n = min(to_boundary, args.steps - done)
                last = trainer.fit(prefetch, n)
                done += n
                if done < args.steps:
                    results = _run_eval(args, cfg, batch_size, mode, model,
                                        trainer,
                                        pspec if mode == "pp" else None,
                                        cache=eval_cache)
                    if results is not None:
                        log_metrics(trainer.global_step, {
                            "step": trainer.global_step,
                            **{f"eval_{k}": v for k, v in results.items()}})
        else:
            last = trainer.fit(prefetch, args.steps)
    finally:
        prefetch.close()
        if close_source is not None:
            close_source()
        if whole_run_trace:
            jax.profiler.stop_trace()
        elif tracer is not None:
            tracer.stop()  # window may still be open on early exit
        if metrics_log:
            metrics_log.close()
        if group is not None:
            unwinding = sys.exc_info()[0] is not None
            if not unwinding:
                try:
                    group.barrier(timeout_s=600)  # all ranks finish first
                except Exception as e:
                    print(f"shutdown barrier skipped: {e}", file=sys.stderr)
            # Unwinding an exception: peers may never arrive — leave at
            # once so survivors' failure detectors see a clean departure
            # and the real error surfaces without a 600 s stall.
            group.leave()
        if coord is not None:
            coord.stop()
    if args.ckpt_dir:
        trainer._save(start_step + args.steps)
        if async_ckpt is not None:
            async_ckpt.wait()
    last.update(placement,
                state_devices=max(device_span(trainer.state), 1),
                state_split_devices=device_span(trainer.state,
                                                 split_only=True))
    if args.eval or args.eval_every:
        results = _run_eval(args, cfg, batch_size, mode, model, trainer,
                            pspec if mode == "pp" else None,
                            cache=eval_cache)
        if results is not None:
            print(json.dumps({"eval": results}), file=sys.stderr)
            last.update({f"eval_{k}": v for k, v in results.items()})
    return last


def _run_eval(args, cfg, batch_size, mode, model, trainer, pspec,
              cache=None):
    """One full pass over the eval split against the CURRENT train state.
    Returns the results dict, or None when the config has no eval split.
    Safe to call repeatedly (--eval-every): the eval SOURCE re-opens each
    time, while the jitted eval step (and the sp eval model) live in
    ``cache`` so repeated passes hit jit's cache instead of retracing."""
    eval_iter, eval_close, stat_fn = _eval_source(args, cfg, batch_size)
    if eval_iter is None:
        return None
    from nezha_tpu.train.eval import evaluate, make_eval_step

    # Graph-engine state stores module-layout params without the
    # variables wrapper; pipeline state stores stacked stage slabs
    # (merged back to the native tree here); sequence-parallel
    # models only run inside shard_map, so eval uses the plain
    # single-device model with the same (replicated) params.
    cache = cache if cache is not None else {}
    eval_model = model
    if args.engine == "graph":
        if "flat" in trainer.state:  # zero1's flat dp-sharded layout
            from nezha_tpu.graph import programs as _programs
            params = _programs.materialize_graph_zero1_params(
                [784, 256, 256, 10], trainer.state)  # mlp_mnist only
        else:
            params = trainer.state["params"]
        variables = {"params": params, "state": {}}
    elif mode == "pp":
        from nezha_tpu.parallel import pipeline as pp_mod
        variables = {"params": pp_mod.merge_pipeline_params(
            pspec, trainer.state["pparams"]), "state": {}}
    else:
        variables = trainer.state["variables"]
        if mode == "sp":
            if "sp_model" not in cache:
                cache["sp_model"] = cfg.build_model()
            eval_model = cache["sp_model"]
    import contextlib

    # gspmd/pp leave params sharded; eval traces fresh (outside the
    # train-step jit), where attn "auto" would otherwise pick the
    # Mosaic flash kernel XLA can't partition over tp/stage shards.
    scope = contextlib.nullcontext()
    if mode in ("gspmd", "pp"):
        from nezha_tpu.parallel.gspmd import auto_partitioner_scope
        scope = auto_partitioner_scope()
    if "step" not in cache:
        cache["step"] = make_eval_step(eval_model, stat_fn)
    try:
        with scope:
            return evaluate(eval_model, variables, eval_iter,
                            stat_fn=stat_fn, max_batches=args.eval_batches,
                            step=cache["step"])
    finally:
        if eval_close is not None:
            eval_close()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="nezha-train",
        description="TPU-native training CLI (configs mirror BASELINE.json)")
    p.add_argument("--config", required=True,
                   choices=["mlp_mnist", "resnet50_imagenet", "gpt2_124m",
                            "bert_base_zero1", "wrn101_large_batch"])
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=None,
                   help="global batch (default: per-config)")
    p.add_argument("--model-preset", choices=["full", "tiny"], default="full",
                   help="tiny = seconds-scale model/data variant of the "
                        "config (same code paths; for tests and smoke runs)")
    p.add_argument("--mesh", default=None,
                   help='mesh axes, e.g. "dp=8" or "dp=2,tp=4" (-1 = rest); '
                        "axes must match what --parallel consumes")
    p.add_argument("--parallel", default="config",
                   choices=["config", "single", "dp", "zero1", "gspmd", "pp",
                            "sp"],
                   help="parallelism strategy: config (per-config default), "
                        "dp (all-reduce), zero1 (sharded optimizer), gspmd "
                        "(dp x tp tensor parallel), pp (dp x pp GPipe "
                        "pipeline), sp (dp x sp ring/Ulysses sequence "
                        "parallel)")
    p.add_argument("--microbatches", type=int, default=4,
                   help="pipeline microbatches per step (--parallel pp)")
    p.add_argument("--sp-flash", default="auto",
                   choices=["auto", "on", "off"],
                   help="ring/ulysses flash kernels: auto = Pallas on TPU "
                        "backends, composed XLA elsewhere; off = force the "
                        "composed fallback (the on-hardware escape hatch); "
                        "on = force flash (interpret mode off-TPU)")
    p.add_argument("--attn-impl", default="ring", choices=["ring", "ulysses"],
                   help="sequence-parallel attention (--parallel sp)")
    p.add_argument("--seq-len", type=int, default=None,
                   help="long-context override for gpt2_124m: sequence "
                        "length for model + data (shard it with "
                        "--parallel sp --mesh dp=X,sp=Y)")
    p.add_argument("--moe-experts", type=int, default=None,
                   help="gpt2_124m only: swap every other block's MLP for "
                        "a top-k routed mixture of this many experts")
    p.add_argument("--optimizer", default=None,
                   choices=["sgd", "momentum", "adamw", "lars", "lamb",
                            "adafactor"],
                   help="swap the config's optimizer (requires --lr; gets "
                        "a warmup+cosine schedule over --steps). The "
                        "config defaults stay the tuned choice.")
    p.add_argument("--lr", type=float, default=None,
                   help="peak learning rate for --optimizer's schedule")
    p.add_argument("--clip-norm", type=float, default=None,
                   help="clip gradients to this global L2 norm before the "
                        "optimizer update (any config/parallel mode)")
    p.add_argument("--grad-accum", type=int, default=None,
                   help="accumulate gradients over N micro-steps before "
                        "each optimizer update (any config/parallel mode; "
                        "effective batch = batch-size x N)")
    p.add_argument("--dropout", type=float, default=None,
                   help="gpt2_124m only: dropout rate override (works in "
                        "every parallel mode incl. pp, where per-(layer, "
                        "microbatch) keys thread through the schedule)")
    p.add_argument("--label-smoothing", type=float, default=None,
                   help="integer-label CE configs (mlp/resnet/wrn): train "
                        "against (1-eps)*one_hot + eps/num_classes")
    p.add_argument("--mlm-mask-token", type=int, default=None,
                   help="bert --data-dir only: [MASK] id (default 103, the "
                        "BERT-wordpiece convention; byte-packed text needs "
                        "an id >= 256 so masks are unambiguous)")
    p.add_argument("--remat", action="store_true",
                   help="gpt2_124m + image configs: rematerialize each "
                        "block/bottleneck in backward (jax.checkpoint) — "
                        "O(1) activation residuals per block for ~1/3 "
                        "extra FLOPs; the long-context / big-batch memory "
                        "knob (pairs with --seq-len and --parallel sp)")
    p.add_argument("--graph-bf16", action="store_true",
                   help="--engine graph, gpt2_124m: author the bf16 "
                        "compute policy in the IR (fp32 master params, "
                        "bf16 GEMMs/activations, fp32 softmax stats and "
                        "logits) — the module policy, in graph form")
    p.add_argument("--wd-exclude-1d", action="store_true",
                   help="AdamW/LAMB configs: exclude ndim<2 leaves (norm "
                        "scales, biases) from decoupled weight decay — "
                        "the standard GPT-2/BERT recipe (module engine; "
                        "not under zero1's flat chunking)")
    p.add_argument("--scan-layers", action="store_true",
                   help="gpt2_124m / bert_base_zero1 (single/dp/zero1/"
                        "gspmd/sp, module engine): layer-stacked trunk via "
                        "lax.scan — one compiled block program instead of "
                        "num_layers inlined copies (params live under "
                        "h_scan / layers_scan with a leading layer dim; "
                        "see GPT2Config.scan_layers)")
    p.add_argument("--grad-allreduce", default="fp32",
                   choices=["fp32", "int8"],
                   help="dp/zero1 gradient wire format: exact fp32 or "
                        "EQuARX/ZeRO++-style block-scaled int8 (~4x less "
                        "ICI traffic; dp all-reduce, zero1 reduce-scatter "
                        "+ update all-gather)")
    p.add_argument("--platform", default=None,
                   help="force a jax platform (e.g. cpu)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--prefetch", type=int, default=2)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=0)
    p.add_argument("--ckpt-keep", type=int, default=None,
                   help="keep only the N newest checkpoints (sharded "
                        "retention counts fully-complete saves only); "
                        "default keeps all")
    p.add_argument("--metrics-file", default=None,
                   help="append JSONL metrics here")
    p.add_argument("--run-dir", default=None,
                   help="telemetry run directory: stream metrics.jsonl + "
                        "spans.jsonl and write a final summary.json "
                        "(step-rate percentiles, per-collective payload "
                        "bytes, compile-cache stats); read it back with "
                        "nezha-telemetry RUN_DIR. With --coordinator each "
                        "process captures into its own rank<K>/ (or "
                        "pid<P>/) subdirectory")
    p.add_argument("--trace-dir", default=None,
                   help="XProf/XLA profiler trace directory (alias for "
                        "--profile-dir; bound the window with "
                        "--profile-steps)")
    p.add_argument("--data-dir", default=None,
                   help="directory with real datasets (train.nzr image "
                        "records / train.tokens.* / mnist IDX); synthetic "
                        "fallback when absent")
    p.add_argument("--crop", type=int, default=224,
                   help="crop size for image-record training")
    p.add_argument("--failure-check-every", type=int, default=10,
                   help="poll the coordinator for dead peers every N steps "
                        "(multi-process runs)")
    p.add_argument("--on-failure", choices=["stop", "rejoin"],
                   default="stop",
                   help="dead-peer response: 'stop' checkpoints then raises "
                        "(supervisor restarts the world and training "
                        "resumes from --ckpt-dir); 'rejoin' additionally "
                        "waits for the crashed rank to be relaunched "
                        "(--rank-hint), reloads the rescue checkpoint, and "
                        "continues in-process")
    p.add_argument("--rejoin-timeout", type=float, default=300.0,
                   help="seconds --on-failure rejoin waits for the "
                        "replacement rank before giving up (then raises, "
                        "checkpoint already committed)")
    p.add_argument("--log-memory", action="store_true",
                   help="add live/peak HBM bytes to every metrics line "
                        "(TPU backends; no-op where the backend exposes "
                        "no memory stats)")
    p.add_argument("--profile-dir", default=None,
                   help="capture an XLA/TPU profiler trace here (whole run "
                        "unless --profile-steps bounds it)")
    p.add_argument("--profile-steps", default=None, metavar="START:COUNT",
                   help="bounded trace into --profile-dir: capture begins "
                        "between two steps, on a drained device before "
                        "step START is dispatched, and covers COUNT whole "
                        "steps (e.g. 10:3 traces steps 10-12 — the "
                        "standard steady-state window)")
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="rendezvous address for multi-process launch")
    p.add_argument("--serve-coordinator", action="store_true",
                   help="also run the coordinator here (rank-0 host)")
    p.add_argument("--world-size", type=int, default=1,
                   help="processes in the job (with --serve-coordinator)")
    p.add_argument("--rank-hint", type=int, default=-1,
                   help="preferred rank (e.g. for restart-in-place)")
    p.add_argument("--no-jax-distributed", action="store_true",
                   help="skip the jax.distributed bootstrap (single-host "
                        "multi-process runs that share no accelerators)")
    p.add_argument("--engine", choices=["module", "graph"], default="module",
                   help="training engine: Module tracing (default) or the "
                        "Graph IR -> StableHLO -> Executor path")
    p.add_argument("--eval", action="store_true",
                   help="run the config's eval split after training")
    p.add_argument("--eval-every", type=int, default=None,
                   help="also run the eval split every N training steps "
                        "(results logged to the metrics stream; implies a "
                        "final --eval pass)")
    p.add_argument("--eval-batches", type=int, default=None,
                   help="cap eval to N batches")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    last = run(args)
    print(json.dumps({"final": last}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
