"""Names of the device program's parts, as ``jax.named_scope`` writes them.

Each scope lands in the ``op_name`` metadata of every HLO instruction
traced inside it, in the forward pass (``jvp(...)``), the remat recompute
(``rematted_computation``) and the backward pass (``transpose(...)``), so
a profiler trace can attribute each device operation to the layer that
issued it.  Metadata only: the compiled program is the same with or
without them.  ``bench/scopes.py`` reads these names from a trace.

| scope | what it wraps |
| --- | --- |
| ``pulse.executor`` | a table executor's scan and its loss reduction |
| ``pulse.hop`` | the ring ``ppermute`` s of a scan step |
| ``pulse.rx_store`` | stores into and reads from the receive buffers |
| ``pulse.stash`` | the skip stash and the turnaround buffer |
| ``pulse.stage_enc`` / ``pulse.stage_dec`` | an encoder / decoder stage body |
| ``pulse.embed`` / ``pulse.head`` | the patch embedding / the loss head |
| ``pulse.loss_allreduce`` | the loss ``psum`` over the mesh |
| ``pulse.zero_gather`` | ZeRO-2 all-gathers of stage parameters |
| ``pulse.optimizer`` | finite check, gradient norm and AdamW update |
| ``attention`` / ``mlp`` / ``skip_proj`` | the model's parts |
"""
from __future__ import annotations

EXECUTOR = "pulse.executor"
HOP = "pulse.hop"
RX_STORE = "pulse.rx_store"
STASH = "pulse.stash"
STAGE_ENC = "pulse.stage_enc"
STAGE_DEC = "pulse.stage_dec"
EMBED = "pulse.embed"
HEAD = "pulse.head"
LOSS_ALLREDUCE = "pulse.loss_allreduce"
ZERO_GATHER = "pulse.zero_gather"
OPTIMIZER = "pulse.optimizer"
ATTENTION = "attention"
MLP = "mlp"
SKIP_PROJ = "skip_proj"

#: every scope, in the order of the table above
ALL = (EXECUTOR, HOP, RX_STORE, STASH, STAGE_ENC, STAGE_DEC, EMBED, HEAD,
       LOSS_ALLREDUCE, ZERO_GATHER, OPTIMIZER, ATTENTION, MLP, SKIP_PROJ)
