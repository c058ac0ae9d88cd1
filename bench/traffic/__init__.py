"""Traffic: one JSON file per mix, one generator module per ``kind``."""
