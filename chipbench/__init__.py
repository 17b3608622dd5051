"""chipbench: the on-chip benchmark of hpx_tpu. See chipbench/README.md."""
