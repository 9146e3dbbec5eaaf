"""repro: 2DReach geosocial reachability queries served from JAX and Pallas."""
__version__ = "0.1.0"
