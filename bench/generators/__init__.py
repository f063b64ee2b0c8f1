"""Traffic generators: each turns a traffic file (`bench/traffic/*.json`)
and a seed into the inputs of one run, through `make(traffic, seed, config)`.
The same seed gives the same inputs; every seed gives the same sizes."""
