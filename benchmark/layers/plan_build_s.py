"""Strategy -> plan: host clock around ``AutoDist(...)`` and
``ad.function(...)``, up to the first step call. Moves ``setup_s``."""


def read(record):
    return record.get("plan_build_s")
