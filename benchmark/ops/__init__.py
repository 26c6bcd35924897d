"""The op modules that run the traffic mixes, one per ``op`` (see
``benchmark/harness.py`` for the five functions each defines)."""
