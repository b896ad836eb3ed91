"""The ``dense`` family: decoder-only transformers whose every layer is
grouped-query attention and a SwiGLU MLP (the Qwen1.5 and Qwen3 blocks).

It binds the harness's modules of this family to the names that
``bench.registry.load_family`` lists: sizes and work counts from
``bench/work.py``, the draw from ``bench/weights.py``, the program's
configuration and parameters from ``bench/program.py``, and the plain
reference from ``bench/reference.py``.  A configuration file without a
``"family"`` key is of this family.
"""
from bench import program, reference, weights, work

sizes = work.Sizes.from_config
model_config = program.model_config
program_params = program.program_params
bench_weights = weights.bench_weights
served_gaps = reference.served_gaps
prefill_flops = work.prefill_flops
decode_step_work = work.decode_step_work
