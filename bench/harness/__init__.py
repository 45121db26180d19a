"""The benchmark's own machinery: loading cells by name, the device
check, weights from the seed, the trace reduction and the peak table."""
