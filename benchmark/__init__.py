"""The benchmark: the yardstick every later PR is held to. See run.py."""
