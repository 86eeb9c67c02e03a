"""Deployments the configurations name, each with its plain reference."""
