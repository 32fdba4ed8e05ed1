"""Model architectures the benchmark serves.  Each module here is one
architecture family and holds, apart from the program under test:

* ``param_count(hf)`` and ``flops_per_token(hf, context)``: sizes from the
  published configuration;
* ``init(key, hf, dtype)``: seeded random weights, drawn on the device in
  one jitted call, laid out as the serving program's parameter tree;
* ``forward(params, hf, tokens, mode)``: the plain reference forward pass
  in float32 (``mode="reference"``), or the lower-precision control
  (``mode="control"``);
* ``program_config(hf, ModelConfig, name)``: the program's config object
  for the same sizes (the class is passed in, so nothing here imports the
  program).
"""
