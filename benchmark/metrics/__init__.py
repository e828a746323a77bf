"""One per-layer reader per file: ``read(run) -> value or None``."""
