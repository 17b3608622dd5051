"""Plain references: they import nothing of hpx_tpu."""
