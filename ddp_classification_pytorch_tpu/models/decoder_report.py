"""What the token decoder says about itself to whoever runs it.

The training loop asks `models/factory.py::model_report` and gets this for
`decoder_lm`: the inputs the model is initialised on, the row length its token
file is cut at, the notes and static counters of what a run built, the
counters of a logged step's routing, and which of an epoch's metrics are
gauges. Every name and help text `docs/observability.md` lists for the decoder
is written here; the predicates the notes come from are the ones the layer
itself dispatches on (`decoder_lm.py`, `ops/moe.py`).
"""

from __future__ import annotations

import collections
from typing import Any, Dict, List, Mapping

import jax.numpy as jnp
import numpy as np

from ..config import ModelConfig
from ..ops.moe import slot_bound
from .decoder_lm import (LOOP_TRACED, flash_backward_path, gdn_core_path,
                         kda_core_path, kda_prepare_path)
from .factory import ModelReport


class DecoderReport(ModelReport):
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.bound = self.slots = 0     # of a routing layer: set by `built`
        self.positions = 0              # of a block-diffusion step: likewise

    def init_inputs(self, image_size: int) -> Any:
        """Token ids; parameters do not depend on T, so a few positions do
        (under block diffusion in whole blocks)."""
        dc = self.cfg.decoder
        few = -(-8 // dc.diffusion_block) * dc.diffusion_block if dc.diffusion else 8
        return jnp.zeros((2, min(dc.seq_len, few)), jnp.int32)

    def token_row_length(self) -> int:
        return self.cfg.decoder.seq_len

    def datasets(self, train_ds, val_ds, seed: int):
        """Under block diffusion the loader noises the rows (data/
        diffusion.py): the training set from the loader's per-row generator,
        the validation set from one keyed by (seed, sample) alone, so that
        every evaluation reads the same noise."""
        dc = self.cfg.decoder
        if not dc.diffusion:
            return train_ds, val_ds
        from ..data.diffusion import NoisedTokens

        return tuple(NoisedTokens(ds, dc.diffusion_block, dc.mask_token,
                                  dc.diffusion_eps, seed, keyed=keyed)
                     for ds, keyed in ((train_ds, False), (val_ds, True)))

    def built(self, rows: int, registry,
              image_size: int = 0) -> Dict[str, Any]:
        """The layout the decoder was built with, for a step of `rows` rows:
        how many of its layers mix tokens by which operator before which
        feed-forward — a static counter in `registry`, and the same counts as
        notes for the set-up line, with the path the attention kernels'
        backward takes at these sizes, whether the delta layers' recurrence
        and their input side take their kernels, what a Gated DeltaNet
        layer's recurrence runs as and the share of the heads held; of a
        looped stack
        also how often a step applies a layer, and how its passes are traced;
        of a routing one the sorted rows a layer keeps / the slots it routes."""
        dc = self.cfg.decoder
        notes: Dict[str, Any] = {}
        kinds = collections.Counter(dc.layer_kinds())
        for (operator, ffn), n in sorted(kinds.items()):
            registry.counter("decoder_layers_total", "layers of the token "
                             "decoder by token mixer and feed-forward",
                             {"operator": operator, "ffn": ffn}).inc(n)
            notes[f"{operator}_{ffn}"] = n
        registry.counter("decoder_layer_applications_total", "layers a step "
                         "runs: the layers built x the passes of the stack "
                         "(--loops)").inc(dc.loops * dc.num_layers)
        if dc.loops > 1:
            notes.update(loops=dc.loops, sandwich=dc.sandwich_norm,
                         passes=LOOP_TRACED)
        if dc.diffusion:
            # the objective, and the mask every attention layer runs under:
            # the two streams' (ops/attention.py::diffusion_mask)
            notes.update(objective=dc.objective, block=dc.diffusion_block,
                         mask_id=dc.mask_token, attn_mask="block_diffusion")
            self.positions = rows * dc.seq_len
        path = flash_backward_path(dc, self.cfg.dtype,
                                   self.cfg.flash_min_tokens)
        if path:
            # what `flash_backward_total{path}` will count once the step is
            # traced (ops/flash_attention.py), known here from the sizes
            notes["flash_backward"] = path
        core = kda_core_path(dc)
        if core:
            # the predicates `ops/kda.py::kda_chunked` and the layer's input
            # side (`DecoderLayer._kda`) dispatch on
            notes.update(kda_core=core, kda_prepare=kda_prepare_path(dc))
        if core := gdn_core_path(dc):
            notes["gdn_core"] = core
        if dc.heads_held:
            registry.counter("decoder_heads_held", "query heads a grouped-"
                             "query or Gated DeltaNet layer computes here: "
                             "--heads_held of --num_heads").inc(dc.heads)
            notes["heads"] = f"{dc.heads}/{dc.num_heads}"
        if dc.moe_layer_names():
            # token-slots k·N a routing layer routes in a step, and the sorted
            # rows it works on while its load fits: ops/moe.py::slot_bound at
            # the step's shapes
            self.slots = rows * dc.positions * dc.top_k
            self.bound = slot_bound(self.slots, dc.held, dc.num_experts)
            notes["moe_bound"] = f"{self.bound}/{self.slots}"
        return notes

    def logged_step(self, metrics: Mapping[str, Any], registry) -> None:
        """The logged step's routing, as the step's metrics carry it —
        `moe_load` (L, e): token-slots each held expert took in each routing
        layer (`DecoderConfig.moe_layer_names`: the layer's index, or "mtp"
        for the prediction module's)."""
        if "masked_tokens" in metrics:
            registry.counter("diffusion_masked_tokens_total", "positions of "
                             "the logged steps' noised rows that held the "
                             "mask id: the ones the block-diffusion loss "
                             "reads").inc(float(metrics["masked_tokens"]))
            registry.counter("diffusion_positions_total", "positions of the "
                             "logged steps' rows (rows x --seq_len): what the "
                             "loss is normalised by").inc(float(self.positions))
        if "moe_load" not in metrics:
            return
        load = np.asarray(metrics["moe_load"])
        for name, row in zip(self.cfg.decoder.moe_layer_names(), load):
            layer = {"layer": name}
            registry.gauge("moe_expert_load_max", "token-slots of the "
                           "busiest held expert in the logged step",
                           layer).set(float(row.max()))
            registry.gauge("moe_expert_load_mean", "mean token-slots of a "
                           "held expert in the logged step",
                           layer).set(float(row.mean()))
            # which path `ops/moe.py::_sparse_experts` took, from the load it
            # asked on the device (under a mesh: the shards' loads together
            # against their bounds together)
            fits = bool(row.sum() <= self.bound)
            for path, took in (("bounded", fits), ("full", not fits)):
                registry.counter("moe_slot_bound_total", "logged steps in "
                                 "which the layer's load on held experts fit "
                                 "the bounded sorted-row buffer / was walked "
                                 "in several windows of it",
                                 dict(layer, path=path)).inc(float(took))
        routed = float(self.slots * len(load))
        for held, n in (("true", float(load.sum())),
                        ("false", routed - float(load.sum()))):
            registry.counter("moe_slots_routed_total", "token-slots the "
                             "routers of the logged steps sent to experts "
                             "held here / elsewhere", {"held": held}).inc(n)

    def epoch_gauges(self, metrics: Mapping[str, float]) -> List[str]:
        """Of an epoch's means, those published as `train_<name>`: the parts
        of a decoder with a prediction module (train_loss = loss_main +
        mtp_weight x loss_mtp), or of a looped one (train_loss = Σ_t
        exit_p<t> x loss_ut<t> over the targets, less exit_beta x the
        entropy of p), or of a block-diffusion one (train_loss_level<q>: the
        weighted loss of the blocks whose level t lies in quartile q)."""
        return sorted(k for k in metrics if k.startswith(("loss_", "exit_p")))
