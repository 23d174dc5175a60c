"""Mean share of the engine's batch slots that held a running request, over
the window's steps: ``engine.stats()["mean_occupancy"]`` (a fraction of
``max_slots``; the engine has taken no step before the window)."""


def read(record):
    return 100.0 * record.clocks["mean_occupancy"]
