"""Load drivers, one per traffic kind, found by name from a cell's file."""
