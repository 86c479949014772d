"""One driver per kind of traffic mix (``kind`` in ``bench/traffic/<mix>.json``)."""
