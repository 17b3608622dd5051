"""Adapters: the one place where the harness reads what the program
does not offer on its public surface."""
