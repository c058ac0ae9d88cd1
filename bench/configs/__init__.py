"""Model configurations, one JSON file each, and their plain reference."""
