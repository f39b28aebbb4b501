"""CPU sizes of the fixture's serve cell: its workload is small already;
weights at std 0.2, as the phi3 serve cells' CPU sizes say why."""

TINY = {"config": {"init": {"std": 0.2}}}
CONTROL = TINY
