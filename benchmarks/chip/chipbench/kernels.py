"""Names by which the benchmark finds the program's device programs in a
trace.  A jitted function's module is named ``jit_<function name>``."""

# kernels/search_pipeline.py: ``_make_fused``'s ``fused`` on one device,
# ``_shard_fused``'s ``per_device`` body under shard_map on several
FUSED_STEP = r"^jit_(fused|per_device)(\(|$|\.)"
