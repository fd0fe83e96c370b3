"""Logical-axis sharding context: the one place mesh layout policy lives.

Models and launch code never name mesh axes directly; they annotate arrays
with *logical* axes ('batch', 'ffn', 'experts', ...) and ask the ``ShardCtx``
to map them.  ``make_rules`` builds the mapping for a concrete mesh + arch:

  * activation rules (``ctx.rules``) drive ``constrain`` /
    ``logical_sharding`` — batch over the data axes (and 'pod' when
    present), tensor-parallel dims over 'model', the KV-cache sequence dim
    over 'data' only for long-context serving;
  * weight rules (``ctx.weight_rules``) drive ``param_sharding`` — TP dims
    over 'model', plus FSDP of the embed dim over 'data' when
    ``serve_fsdp`` (always on for training);
  * the serve 2-D MoE layout (``serve_fsdp=False``) flips experts onto the
    token ('data') axis with second-level TP on the expert ff dim —
    consumed by models/moe.py.

A ``ShardCtx(None, {}, {})`` is the disabled single-device context:
``constrain`` is the identity and every ``axis_size`` is 1, so model code is
mesh-agnostic without branching.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_data_mesh(num_devices: int, axis: str = "data") -> Mesh:
    """1-D mesh over the first ``num_devices`` local devices.

    The graph engines (``repro.core.distributed``) partition destination
    intervals over this single axis; the model stack builds its own 2-D
    meshes via ``make_rules``.  Raises with the CPU-emulation hint when the
    process has fewer devices than requested (jax locks the device count at
    first init, so the flag must be set before importing jax).
    """
    if num_devices < 1:
        raise ValueError(f"num_devices must be >= 1, got {num_devices}")
    devices = jax.devices()
    if num_devices > len(devices):
        raise RuntimeError(
            f"num_devices={num_devices} but only {len(devices)} jax "
            f"device(s) are visible; on CPU launch with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={num_devices} "
            f"(set before jax initializes)")
    return Mesh(np.asarray(devices[:num_devices]), (axis,))

# a rule value: one mesh axis name, a tuple of them (e.g. ('pod', 'data')),
# or None for replicated
Rule = Any


def _axes_tuple(rule: Rule) -> tuple[str, ...]:
    if rule is None:
        return ()
    if isinstance(rule, str):
        return (rule,)
    return tuple(rule)


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    mesh: Mesh | None
    rules: Mapping[str, Rule]         # activation logical axis -> mesh axes
    weight_rules: Mapping[str, Rule]  # parameter logical axis -> mesh axes
    ep_mode: str = "a2a"              # 'a2a' | 'replicated' (models/moe.py)

    @property
    def enabled(self) -> bool:
        return self.mesh is not None

    # -- sizes -----------------------------------------------------------
    def axis_size(self, logical: str) -> int:
        """Total device count the logical axis is split over (1 if replicated)."""
        if not self.enabled:
            return 1
        return math.prod(self.mesh.shape[a]
                         for a in _axes_tuple(self.rules.get(logical)))

    # -- spec construction ----------------------------------------------
    def _spec(self, logical_axes, rules: Mapping[str, Rule],
              shape=None) -> P:
        """Map logical dim names to a PartitionSpec.

        A mesh axis may appear at most once in a spec; when ``shape`` is
        known, a dim that the mesh axis does not divide evenly stays
        replicated (reduced test configs have tiny dims).
        """
        used: set[str] = set()
        out: list[Rule] = []
        for i, name in enumerate(logical_axes):
            rule = rules.get(name) if name is not None else None
            axes = _axes_tuple(rule)
            if axes and not (used & set(axes)):
                size = math.prod(self.mesh.shape[a] for a in axes)
                if shape is None or (size and shape[i] % size == 0):
                    used.update(axes)
                    out.append(rule if isinstance(rule, str) else tuple(axes))
                    continue
            out.append(None)
        return P(*out)

    def logical_sharding(self, logical_axes) -> NamedSharding | None:
        """NamedSharding for an activation/input tree leaf (None if disabled)."""
        if not self.enabled:
            return None
        return NamedSharding(self.mesh, self._spec(logical_axes, self.rules))

    def param_sharding(self, param) -> NamedSharding | None:
        """NamedSharding for a Param-annotated weight (by its logical axes)."""
        if not self.enabled:
            return None
        axes = tuple(param.axes or ())
        shape = tuple(getattr(param.value, "shape", ()) or ())
        if len(axes) != len(shape):
            axes = axes + (None,) * (len(shape) - len(axes))
        return NamedSharding(
            self.mesh, self._spec(axes[: len(shape)], self.weight_rules, shape))

    def constrain(self, x, logical_axes):
        """with_sharding_constraint by logical axes; identity when disabled."""
        if not self.enabled:
            return x
        spec = self._spec(logical_axes, self.rules, shape=x.shape)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))


# ---------------------------------------------------------------------------
def make_rules(mesh: Mesh | None, cfg, *, long_context: bool = False,
               ep_mode: str = "a2a", serve_fsdp: bool = True) -> ShardCtx:
    """Derive the logical->mesh mapping for one (mesh, arch, variant) cell.

    ``mesh=None`` yields the disabled single-device context."""
    if mesh is None:
        return ShardCtx(None, {}, {}, ep_mode=ep_mode)
    names = tuple(mesh.axis_names)
    model = "model" if "model" in names else None
    data_axes = tuple(a for a in ("pod", "data") if a in names)
    batch: Rule = (data_axes if len(data_axes) > 1
                   else (data_axes[0] if data_axes else None))
    data = "data" if "data" in names else None

    rules: dict[str, Rule] = {
        "batch": batch,
        "seq": None,                 # activations keep seq replicated;
        "kv_seq": (data if long_context else None),  # ...KV caches may not
        "embed": None,
        "ffn": model,
        "swiglu": model,
        "geglu": model,
        "q_heads": model,
        "kv_heads": None,            # few KV heads: replicate, repeat for TP
        "head_dim": None,
        "lstm_heads": model,
        "mamba_inner": model,
        "vocab": model,
        "experts": model,
    }

    weight_rules: dict[str, Rule] = {
        "layers": None,
        # FSDP over the data axes: on for training and the default serve
        # layout, off for the 2-D expert serve variant
        "embed": (batch if serve_fsdp else None),
        "ffn": model,
        "swiglu": model,
        "geglu": model,
        "q_heads": model,
        "kv_heads": None,
        "head_dim": None,
        "lstm_heads": model,
        "mamba_inner": model,
        "vocab": model,
        "experts": model,
        "expert_ff": None,
    }
    if not serve_fsdp and data is not None and model is not None:
        # serve 2-D MoE layout: experts over the token axis, second-level TP
        # on the expert ff dim (models/moe.py routes around the a2a for it)
        rules["experts"] = data
        weight_rules["experts"] = data
        weight_rules["expert_ff"] = model

    return ShardCtx(mesh, rules, weight_rules, ep_mode=ep_mode)
