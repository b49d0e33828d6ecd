"""One module per architecture: ``bench/arch/<model_type>.py``.

A configuration file names its architecture by ``model_type``, the key of
the published ``config.json``. ``bench.model.load_arch`` loads the module
of that name by its path, from the checkout the cell is read from, so a new
architecture arrives as a new file and no list of imports changes. Each
module provides the functions named in ``INTERFACE``:

- ``param_shapes(cfg)``: the weight tree of shapes, ``{name: shape}``,
  laid out as the program's parameter tree expects it;
- ``fan_in(name, shape)``: the fan-in of a weight matrix, which sets its
  init scale (``bench.model.make_weights``);
- ``arch_config(cfg, name)``: the program's ``ArchConfig``;
- ``logits_at(weights, cfg, tokens, positions, precision="float32")``: the
  plain reference forward, in float32 at ``highest`` matmul precision,
  yielding ``(row slice, logits)`` blocks at ``positions`` of ``tokens``;
  with ``precision="fp8"`` the control (float8 weights, bfloat16
  activations). It imports nothing of the program;
- the work one token needs, from the configuration and the context length
  alone (``bench/work.py`` sums them): ``matmul_flops(cfg)``, the
  matrix-product FLOPs of one token through every layer outside
  attention; ``attn_flops(cfg, ctx)`` and ``attn_bytes(cfg, ctx)``, of one
  query over ``ctx`` cached positions in every layer; ``head_flops(cfg)``,
  of the output head for one token; and ``step_weight_bytes(cfg)``, the
  weight bytes one device step reads.
"""

INTERFACE = ("param_shapes", "fan_in", "arch_config", "logits_at",
             "matmul_flops", "attn_flops", "attn_bytes", "head_flops",
             "step_weight_bytes")
