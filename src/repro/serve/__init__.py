# Serving substrate: KV caches, slot-based continuous batching for the
# LM path (per-slot cache commit masks), and the multi-tenant bucketed
# ViG image engine (DESIGN.md §9): fixed slots, request batches padded
# to a static bucket set, one donated jax.jit + per-slot functional
# DigcState rows per bucket (per-bucket VigSchedule autotuning).
