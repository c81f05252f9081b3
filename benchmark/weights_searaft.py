"""Weights from ``--seed`` for a model with leaves ``benchmark/weights.py``
has no rule for: the ConvNeXt blocks' ``gamma``.

SEA-RAFT initialises ``gamma = 1e-6`` (core/layer.py ``ConvNextBlock``,
``layer_scale_init_value``), which makes ``gamma * W2 gelu(W1 LN(dw(u)))``
inert: a run that dropped the depthwise convolution, the LayerNorm and both
products would still agree with the reference.  So the benchmark draws
``gamma`` from U(0.5, 1.5) from the seed (listed under ``assumed`` in the
configuration file).  That is the one rule ``weights_gma.make_variables``
adds for GMA's leaf of the same name, so it is that function: every other
leaf is ``weights.make_variables``'s, from the same keys -- Kaiming-normal
kernels in the two ResNet trunks, torch's default elsewhere (the depthwise
kernel's fan-in is its 49 taps), norm scales and statistics a little off 1
and 0.
"""

from benchmark.weights_gma import make_variables  # noqa: F401
