"""Embedder fine-tuning — the paper's training recipe as a Trainer.

Defaults are the paper's hyperparameters (§3 Experimental Setup):
one epoch, lr = 6.5383156211679e-5, batch 16, Adam, max grad norm 0.5,
online contrastive loss.  The 1-epoch + clipped-norm discipline is the
catastrophic-forgetting control of §3.2 — ``epochs`` is a knob precisely
so the forgetting benchmark can turn it up to 6 and show the damage.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.losses import contrastive_loss, online_contrastive_loss
from repro.core.metrics import pair_classification_metrics
from repro.data.corpora import PairDataset
from repro.data.pairs import iter_batches, tokenize_pairs
from repro.data.tokenizer import HashTokenizer
from repro.models import encode as encode_lm
from repro.models import init_lm, split
from repro.obs.trace import child
from repro.training.optim import adam, apply_updates


@dataclass
class FinetuneConfig:
    epochs: int = 1
    lr: float = 6.5383156211679e-5
    batch_size: int = 16
    max_grad_norm: Optional[float] = 0.5
    margin: float = 0.5
    loss: str = "online"          # 'online' | 'contrastive'
    max_len: int = 32
    seed: int = 0
    log_every: int = 50


class EmbedderTrainer:
    def __init__(self, model_cfg: ModelConfig, ft: FinetuneConfig = None,
                 params=None):
        assert model_cfg.is_encoder, "embedder must be an encoder config"
        self.cfg = model_cfg
        self.ft = ft or FinetuneConfig()
        if params is None:
            params, _ = split(init_lm(model_cfg,
                                      jax.random.PRNGKey(self.ft.seed)))
        self.params = params
        init_opt, self._update = adam(self.ft.lr,
                                      max_grad_norm=self.ft.max_grad_norm)
        self.opt_state = init_opt(self.params)
        loss_fn = (online_contrastive_loss if self.ft.loss == "online"
                   else contrastive_loss)

        def step(params, opt_state, batch):
            def objective(p):
                # one stacked forward for both sides of every pair
                toks = jnp.concatenate([batch["tok1"], batch["tok2"]], axis=0)
                masks = jnp.concatenate([batch["mask1"], batch["mask2"]],
                                        axis=0)
                embs = encode_lm(p, self.cfg, toks, masks)
                e1, e2 = jnp.split(embs, 2, axis=0)
                return loss_fn(e1, e2, batch["label"], margin=self.ft.margin)

            loss, grads = jax.value_and_grad(objective)(params)
            updates, opt_state, om = self._update(grads, opt_state, params)
            params = apply_updates(params, updates)
            return params, opt_state, {"loss": loss, **om}

        def encode(params, ids, mask):
            return encode_lm(params, self.cfg, ids, mask)

        self._step = jax.jit(step)
        self._encode = jax.jit(encode)
        self.history: List[dict] = []

    # ------------------------------------------------------------------
    def fit(self, train: PairDataset, tokenizer: HashTokenizer,
            eval_ds: Optional[PairDataset] = None) -> dict:
        arrays = tokenize_pairs(train, tokenizer, self.ft.max_len)
        t0 = time.perf_counter()
        n_steps = 0
        for batch in iter_batches(arrays, self.ft.batch_size,
                                  seed=self.ft.seed, epochs=self.ft.epochs):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            self.params, self.opt_state, m = self._step(
                self.params, self.opt_state, batch)
            n_steps += 1
            if n_steps % self.ft.log_every == 0:
                self.history.append(
                    {"step": n_steps, "loss": float(m["loss"])})
        out = {"steps": n_steps, "train_seconds": time.perf_counter() - t0}
        if eval_ds is not None:
            out["eval"] = self.evaluate(eval_ds, tokenizer)
        return out

    # ------------------------------------------------------------------
    def embed_texts(self, texts, tokenizer: HashTokenizer,
                    batch_size: int = 64) -> np.ndarray:
        """(len(texts), D) unit-norm embeddings, encoded in chunks of
        ``batch_size`` rows.  Each chunk is padded with empty strings to
        the next power of two of its rows, ``batch_size`` at most, so
        the encoder compiles one shape per bucket (1, 2, 4, ...) and a
        one-question call runs a one-row forward.  Rows never interact
        in the encoder: the padding changes no real row."""
        out = []
        for i in range(0, len(texts), batch_size):
            chunk = list(texts[i:i + batch_size])
            n = len(chunk)
            pad_to = min(1 << (n - 1).bit_length(), batch_size)
            with child("embed.tokenize", rows=n, padded_to=pad_to):
                ids, mask = tokenizer.encode_batch(
                    chunk + [""] * (pad_to - n), self.ft.max_len)
            e = self._encode(self.params, jnp.asarray(ids), jnp.asarray(mask))
            with child("embed.sync"):
                e = np.asarray(e)
            out.append(e[:n])
        return np.concatenate(out, axis=0)

    def pair_scores(self, ds: PairDataset, tokenizer: HashTokenizer
                    ) -> np.ndarray:
        e1 = self.embed_texts(ds.q1, tokenizer)
        e2 = self.embed_texts(ds.q2, tokenizer)
        return np.sum(e1 * e2, axis=-1)

    def evaluate(self, ds: PairDataset, tokenizer: HashTokenizer) -> dict:
        scores = self.pair_scores(ds, tokenizer)
        return pair_classification_metrics(scores, ds.labels)

    def make_embed_fn(self, tokenizer: HashTokenizer) -> Callable:
        """list[str] -> (B, D) unit-norm np — plugs into CachedLLMService.
        ``embed_texts`` at its default ``batch_size`` of 64: the chunk
        size and the largest bucket; a call's rows are padded to the next
        power of two."""
        return lambda texts: self.embed_texts(texts, tokenizer)
