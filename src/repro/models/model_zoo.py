"""Unified model API over all architecture families + the assigned
input-shape grid (40 arch × shape cells)."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec as encdec_mod
from repro.models import transformer as tf_mod
from repro.models.common import dtype_of


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str       # train | prefill | decode
    seq: int
    batch: int


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeSpec) -> Tuple[bool, str]:
    """Assignment rules: long_500k needs sub-quadratic attention."""
    if shape.name == "long_500k":
        if cfg.family in ("ssm", "hybrid"):
            return True, "SSM/hybrid: O(1)-state decode"
        if cfg.local_global_ratio:
            return True, "5:1 sliding-window local attention"
        return False, "pure full-attention arch at 500k ctx (per assignment)"
    return True, ""


@dataclasses.dataclass
class ModelAPI:
    cfg: ModelConfig
    init: Callable[[jax.Array], Any]
    loss_fn: Callable[[Any, Dict[str, jax.Array]], jax.Array]
    cache_init: Callable[[int, int], Any]
    prefill: Callable[..., Tuple[jax.Array, Any]]
    decode_step: Callable[..., Tuple[jax.Array, Any]]

    # ---- ShapeDtypeStruct stand-ins for the dry-run ----
    def train_batch_specs(self, shape: ShapeSpec) -> Dict[str, jax.ShapeDtypeStruct]:
        b, s = shape.batch, shape.seq
        specs = {
            "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }
        specs.update(self._frontend_specs(b))
        return specs

    def _frontend_specs(self, b: int) -> Dict[str, jax.ShapeDtypeStruct]:
        dt = dtype_of(self.cfg)
        if self.cfg.family == "vlm":
            return {"patches": jax.ShapeDtypeStruct((b, self.cfg.num_patches, 1024), dt)}
        if self.cfg.family == "encdec":
            return {
                "frames": jax.ShapeDtypeStruct((b, self.cfg.encoder_seq, self.cfg.d_model), dt)
            }
        return {}

    def decode_token_specs(self, shape: ShapeSpec) -> Dict[str, jax.ShapeDtypeStruct]:
        return {"tokens": jax.ShapeDtypeStruct((shape.batch, 1), jnp.int32)}

    def make_train_batch(self, key, shape: ShapeSpec) -> Dict[str, jax.Array]:
        """Materialized synthetic batch (smoke tests / examples)."""
        b, s = shape.batch, shape.seq
        k1, k2 = jax.random.split(key)
        batch = {
            "tokens": jax.random.randint(k1, (b, s), 0, self.cfg.vocab_size, jnp.int32),
            "labels": jax.random.randint(k2, (b, s), 0, self.cfg.vocab_size, jnp.int32),
        }
        dt = dtype_of(self.cfg)
        if self.cfg.family == "vlm":
            batch["patches"] = jnp.ones((b, self.cfg.num_patches, 1024), dt)
        if self.cfg.family == "encdec":
            batch["frames"] = jnp.ones((b, self.cfg.encoder_seq, self.cfg.d_model), dt)
        return batch


def build_model(cfg: ModelConfig) -> ModelAPI:
    if cfg.family == "encdec":
        return ModelAPI(
            cfg=cfg,
            init=lambda key: encdec_mod.encdec_init(cfg, key),
            loss_fn=lambda p, b: encdec_mod.encdec_loss(p, b, cfg),
            cache_init=lambda batch, max_seq: encdec_mod.cache_init(cfg, batch, max_seq),
            prefill=lambda p, b, c: encdec_mod.prefill(p, b, c, cfg),
            decode_step=lambda p, t, c, pos: encdec_mod.decode_step(p, t, c, pos, cfg),
        )
    return ModelAPI(
        cfg=cfg,
        init=lambda key: tf_mod.lm_init(cfg, key),
        loss_fn=lambda p, b: tf_mod.lm_loss(p, b, cfg),
        cache_init=lambda batch, max_seq: tf_mod.cache_init(cfg, batch, max_seq),
        prefill=lambda p, b, c, **kw: tf_mod.prefill(p, b, c, cfg, **kw),
        decode_step=lambda p, t, c, pos: tf_mod.decode_step(p, t, c, pos, cfg),
    )
