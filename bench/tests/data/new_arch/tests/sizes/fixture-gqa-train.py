"""CPU sizes of the fixture's train cell: its workload is small already;
the step in float32, as the phi3 train cell's CPU sizes say why."""

TINY = {"config": {"program": {"param_dtype": "float32",
                               "activation_dtype": "float32"}}}
CONTROL = TINY
