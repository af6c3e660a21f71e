"""Window drivers, one per workload ``kind``; ``run.py`` finds them by
the name before the first underscore (``serve_closed`` -> ``serve``)."""
