"""From a configuration file to what the program is built from.

A configuration file holds the model's sizes under the keys of the source's
own ``config.json``; ``families/<model_type>.json`` says which preset of the
program the family starts from and which of its fields each key sets. No
preset is added to the program: the model is built through
``preset`` + ``preset_overrides`` (serve) or ``presets.<preset>(**overrides)``
(train). Dtypes travel as names, because the command's own process never
imports JAX.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

def program_overrides(cfg: Dict[str, Any],
                      fam: Dict[str, Any]) -> Tuple[str, Dict[str, Any]]:
    """(preset name, overrides) for ``ray_tpu.models.presets``."""
    overrides = dict(fam.get("constants", {}))
    for key, field in fam["keys"].items():
        if cfg.get(key) is not None:
            overrides[field] = cfg[key]
    overrides.update(cfg.get("program", {}))
    return fam["preset"], overrides


def build_program_config(preset: str, overrides: Dict[str, Any]):
    """The program's ``TransformerConfig`` (worker side; imports JAX)."""
    import jax.numpy as jnp

    from ray_tpu.models import presets

    return getattr(presets, preset)(**with_dtypes(overrides, jnp))


def with_dtypes(overrides: Dict[str, Any], jnp) -> Dict[str, Any]:
    out = dict(overrides)
    for k in ("dtype", "param_dtype"):
        if isinstance(out.get(k), str):
            out[k] = getattr(jnp, out[k])
    return out


def program_sizes(pcfg) -> Dict[str, Any]:
    """The sizes the yardstick's arithmetic needs, read off the program's
    config once it is built (plain numbers, JSON-able)."""
    return {"vocab_size": pcfg.vocab_size, "num_layers": pcfg.num_layers,
            "embed_dim": pcfg.embed_dim, "num_heads": pcfg.num_heads,
            "num_kv_heads": pcfg.kv_heads, "head_dim": pcfg.head_dim,
            "mlp_dim": pcfg.hidden_dim, "mlp": pcfg.mlp,
            "max_seq_len": pcfg.max_seq_len}
