"""Drivers: one per kind of system under test; a configuration names its own."""
