"""Metric readers: ``<metric name>.py`` holds ``read(run)`` for that metric."""
