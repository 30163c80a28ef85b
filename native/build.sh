#!/bin/sh
# Build the native loader shared library.
set -e
cd "$(dirname "$0")"
g++ -O3 -std=c++17 -shared -fPIC -o ../tidb_tpu/storage/_native.so loader.cpp
echo "built tidb_tpu/storage/_native.so"
