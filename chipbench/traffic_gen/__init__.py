"""Traffic generators: each reads one data file of chipbench/traffic/."""
