"""Drivers, one per kind of traffic: how a cell's traffic is fed to the
program, what its window measures and how its outputs are checked."""
