"""Pallas TPU kernels: flash_attention (blocked causal attention),
rwkv6_scan (chunk-parallel WKV with data-dependent decay), dt_pack
(datatype pack/unpack engine). Each has a pure-jnp oracle in ref.py and
a wrapper in ops.py; ops.resolve_interpret runs them in the interpreter
on the CPU backend and compiles them everywhere else."""
