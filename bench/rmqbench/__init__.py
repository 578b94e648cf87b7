"""The RMQ benchmark's yardstick: data, reference, byte counts, traces."""
