"""Shared parts of the chip benchmark: spans, trace reduction, the config
guard and the result line.  Everything that belongs to one configuration,
traffic mix or metric lives in its own file and is found by name."""
