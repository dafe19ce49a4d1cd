"""Drivers, one per way of driving the program, named by a cell's traffic."""
