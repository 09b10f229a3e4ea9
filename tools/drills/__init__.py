"""Fault drills and their rigs: ``soak`` (the drills), ``load_gen`` and
``fleet`` (the client and replica rigs they drive), ``smoke`` (``make
api-test``). A drill holds the system to a guarantee; none measures speed."""
