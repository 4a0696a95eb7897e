"""Model code: configs, layers, the stacked families and the model facade.

``LAYER_SCOPES`` are the ``jax.named_scope`` names that mark a train step's
layers.  Every HLO instruction of a compiled step carries the scopes it was
traced under in its ``op_name`` metadata; ``repro.obs.programs`` reads them
back, so a profile's ops can be charged to the layer that caused them.
Scopes change metadata only: the compiled program is the same instruction
for instruction."""

LAYER_SCOPES = ("mlstm", "slstm", "lm_head", "optimizer")
