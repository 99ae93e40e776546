"""Drivers, one per traffic kind: ``run(ctx) -> Record``."""
