"""Traffic generators. A traffic mix (``bench/traffic/<name>.json``)
names one of these modules under ``"generator"``; the module's
``make(config, traffic, seed)`` builds the cell."""
