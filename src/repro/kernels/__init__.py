"""Pallas TPU kernels for the framework's compute hot-spots.

Three kernels, each with the standard layout (<name>.py kernel with
pl.pallas_call; ops.py jit'd wrapper; ref.py pure-jnp oracle):

  minplus/        min-plus DP transition for the pareto-optimal scheduler
                  (transition matrix generated in-registers: O(N^2) compute
                  on O(N) HBM traffic)
  spork_predict/  Alg. 2 expected-objective scan over candidates x bins
  decode_attn/    GQA flash-decode attention with online softmax over KV
                  blocks (the serving engine's hot-spot)

backend.py owns the execution mode: `use_interpret()` is False on a TPU,
where every kernel compiles through Mosaic (a lowering failure raises),
and True elsewhere. Every kernel lowers for TPU v5e
(tests/test_tpu_compile.py compiles each one for a described chip).
"""

