"""Packaging for dask_sql_tpu (reference: /root/reference/setup.py console
scripts at :106-111; no jar build step).  The native C++ parser is built
from ``native/`` on first import of a checkout (dask_sql_tpu/native); an
installed package without it is served by the Python parser."""
from setuptools import find_packages, setup


setup(
    name="dask_sql_tpu",
    version="0.1.0",
    description="TPU-native distributed SQL query engine (dask-sql capability parity)",
    packages=find_packages(include=["dask_sql_tpu", "dask_sql_tpu.*"]),
    python_requires=">=3.10",
    install_requires=[
        "jax",
        "numpy",
        "pandas",
    ],
    extras_require={
        "dev": ["pytest"],
        "ml": ["scikit-learn", "joblib"],
        "cli": ["prompt_toolkit", "pygments"],
    },
    entry_points={
        "console_scripts": [
            "dask-sql-tpu = dask_sql_tpu.cmd:main",
            "dask-sql-tpu-server = dask_sql_tpu.server.app:main",
        ]
    },
)
