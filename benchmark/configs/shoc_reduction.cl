/* SHOC 1.1.5 (Danalis et al., GPGPU-3 2010), src/opencl/level1/reduction/reduction.cl,
 * kernel `reduce`, written out from memory (the file is not in the container:
 * shoc_reduction.json, "assumed").  Every work-item walks the input with the grid's
 * stride, adding TWO elements a pass into its slot of the group's __local tile; the
 * group meets at a barrier, halves the tile eight times with a barrier after every
 * halving, and work-item 0 writes the group's partial.  The host adds the partials.
 * SHOC passes the tile as a `__local FPTYPE*` parameter sized by the host; upstream
 * Cekirdekler binds every pointer parameter to an array of the caller's, so the tile
 * is declared in the kernel; FPTYPE is written out as float. */
__kernel void reduce(__global const float *g_idata, __global float *g_odata, const unsigned int n)
{
    __local float sdata[256];
    const unsigned int tid = get_local_id(0);
    unsigned int i = (get_group_id(0) * (get_local_size(0) * 2)) + tid;
    const unsigned int gridSize = get_local_size(0) * 2 * get_num_groups(0);
    const unsigned int blockSize = get_local_size(0);
    sdata[tid] = 0;
    while (i < n) { sdata[tid] += g_idata[i] + g_idata[i + blockSize]; i += gridSize; }
    barrier(CLK_LOCAL_MEM_FENCE);
    for (unsigned int s = blockSize / 2; s > 0; s >>= 1) {
        if (tid < s) { sdata[tid] += sdata[tid + s]; }
        barrier(CLK_LOCAL_MEM_FENCE);
    }
    if (tid == 0) { g_odata[get_group_id(0)] = sdata[0]; }
}
