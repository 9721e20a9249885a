"""Window drivers, one module per kind of cell (``workload["kind"]``)."""
