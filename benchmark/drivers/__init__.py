"""Traffic drivers, found by name: ``Driver(config, params, seed)``."""
