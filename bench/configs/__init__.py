"""Configurations (``<name>.json``) and the modules that run them."""
