"""On-chip benchmark of the cascaded VFL system (see ``bench/run.py``)."""
