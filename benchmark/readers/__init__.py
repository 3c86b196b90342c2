"""Per-layer metric readers, found by name: ``read(args, ctx)``."""
